"""The Granite-4.0-H cell's files: the manifest with the new entries, the
configuration against the catalog, the runner end to end on the CPU at a tiny
size (sound run correct, controls read beside it and failing), the held state
bytes, and the work functions and readers against counts worked by hand."""
import json
import os
import time

import numpy as np
import pytest

from benchmark import flops_granite_hybrid as flops
from benchmark import manifest, run, serve_granite
from benchmark import weights_granite_hybrid as W
from benchmark.kernels import paged_attention_granite as k_pa
from benchmark.kernels import ssm_update_granite as k_ssm
from benchmark.readers import delta_ratio, mfu_granite_hybrid

from bh_tiny import REPO
from bh_tiny_granite import CELL, make_granite_root

NAME = "granite4h-micro-gen1k-batch"
#: the .gen twins, in the order of their entries
TWINS = ["tick_mean_ms", "tick_host_ms", "tick_sync_ms", "decode_batch_mean",
         "admit_blocked_slots_share", "first_token_sync_ms", "tick_stage_ms",
         "tick_book_ms"]
NEW_METRICS = [t + ".gen1k" for t in TWINS] + [
    "chunk_tick_share.gen1k", "state_carry_share.gen1k", "mfu.gen1k",
    "ssm_update_roofline.gen1k", "paged_attn_roofline.gen1k"]


def real_cfg():
    with open(os.path.join(REPO, "benchmark", "configs", "granite-4.0-h-micro.json")) as f:
        return json.load(f)


def _spec(name):
    with open(os.path.join(REPO, "benchmark", "metrics", name + ".json")) as f:
        return json.load(f)


def test_the_manifest_checks_out_with_the_new_entries():
    m = manifest.load_manifest(REPO)
    manifest.check_manifest(m, REPO)
    cell = manifest.load_cell(REPO, NAME)
    assert cell["cell"]["chips"] == 1 and cell["job"]["kind"] == "serve_granite"
    assert cell["cell"]["traffic"] == "gen1k-batch"
    assert cell["job"]["engine"] == {"max_batch_slots": 64, "max_seq_len": 1664,
                                     "page_size": 128, "num_pages": 833,
                                     "prefill_chunk": 256}
    assert cell["traffic"] == {
        "loop": "closed", "clients": 96, "pool": 4096, "block": 64,
        "schedule_seed": 37, "prompt": {"dist": "uniform", "min": 256, "max": 1024},
        "output": {"dist": "uniform", "min": 256, "max": 512}}
    # every slot can hold its longest request: 13 pages, and the trash page
    eng = cell["job"]["engine"]
    assert eng["max_seq_len"] >= 1024 + 512 + 1 and eng["max_seq_len"] % 128 == 0
    assert eng["num_pages"] == 64 * eng["max_seq_len"] // 128 + 1
    assert cell["job"]["check_pad_to"] == 1536 and cell["job"]["check_requests"] == 6
    assert {e["name"] for e in cell["end_to_end"]} == {"out_tokens_per_s", "setup_s"}
    assert {e["name"] for e, _ in cell["per_layer"]} == set(NEW_METRICS) | {"window_compiles"}
    assert all(e["moves"] == "out_tokens_per_s" and e["workloads"] == [NAME]
               for e, _ in cell["per_layer"] if e["name"] in NEW_METRICS)
    entry = next(c for c in m["configs"] if c["name"] == cell["cell"]["config"])
    assert entry["reduced"] == []
    # appended after what PR 36 had, nothing moved
    assert [w["name"] for w in m["workloads"]][5] == NAME
    assert [c["name"] for c in m["configs"]][4] == "granite-4.0-h-micro"
    names = [x["name"] for x in m["per_layer"]]
    at = names.index(NEW_METRICS[0])
    assert at == 1 + names.index("chunk_tick_share.batch")
    assert names[at:at + len(NEW_METRICS)] == NEW_METRICS
    out_tps = next(e for e in m["end_to_end"] if e["name"] == "out_tokens_per_s")
    assert out_tps["bound"] == 0.023 and out_tps["workloads"][3:5] == [
        "kanana2-docqa16k-batch", NAME]
    # the twins' specs, the cell's own tag apart
    for t in TWINS:
        assert _spec(t + ".gen1k") == _spec(t + ".gen")
    assert _spec("chunk_tick_share.gen1k") == _spec("chunk_tick_share.batch")


def test_the_configuration_is_the_catalogs_with_nothing_cut():
    cfg = real_cfg()
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "granite-4.0-h-micro")
    assert {k for k, v in row["config"].items() if cfg.get(k) != v} == set()
    assert cfg["source"] == row["source_url"]
    assert cfg["reduced"] == {} and cfg["published"] == {}
    assert {"ssm_state", "weights", "eos", "positions"} <= set(cfg["assumed"])


def test_the_parameter_count_and_the_state_at_the_configurations_sizes():
    """Shapes only: nothing is allocated."""
    cfg = real_cfg()
    per = {k: sum(int(np.prod(s)) for s in W.layer_shapes(cfg, k).values())
           for k in ("mamba", "attention")}
    mlp = 2048 * 16384 + 8192 * 2048 + 2 * 2048
    assert per["mamba"] == 2048 * 8512 + 4 * 4352 + 4352 + 3 * 64 + 4096 \
        + 4096 * 2048 + mlp
    assert per["attention"] == 2 * 2048 * 2048 + 2 * 2048 * 512 + mlp
    n = W.n_params(cfg)
    assert n == 36 * per["mamba"] + 4 * per["attention"] + 100352 * 2048 + 2048
    assert round(n / 1e9, 2) == 3.19 and round(2 * n / 1e9, 2) == 6.38
    assert serve_granite.state_bytes(cfg, 64) == 64 * 36 * (
        64 * 64 * 128 * 4 + 3 * 4352 * 2) == 4_892_000_256


def test_flops_and_kernel_work_against_sizes_worked_by_hand():
    cfg = real_cfg()
    rec = 5 * 64 * 64 * 128 + 4 * 4096
    assert flops.recurrence_token_flops(cfg) == rec
    m = 2 * 2048 * 8512 + 2 * 4096 * 2048 + 2 * 4 * 4352 + rec
    a = 4 * 2048 * (32 + 8) * 64
    mlp = 6 * 2048 * 8192
    assert flops.serve_flops(cfg, 1, 0, 0) == 36 * m + 4 * a + 40 * mlp
    assert flops.serve_flops(cfg, 0, 1, 0) == 2 * 2048 * 100352   # the tied head
    assert flops.serve_flops(cfg, 0, 0, 1) == 4 * 32 * 64 * 4
    # a decode tick of 62 rows: 36 layers of 2 x 2 MB of state each
    f, b = k_ssm.work(cfg, 62)
    assert f == 5 * 64 * 64 * 128 * 62 * 36
    assert b == (2 * 64 * 64 * 128 + 4096) * 4 * 62 * 36
    assert 9.3e9 < b < 9.5e9                     # ISSUE: ~9.4 GB a tick
    # 62 rows at ~830 tokens of context: 2 KiB a token a layer, 4 layers
    w = k_pa.work(cfg, 62, 62 * 830, [(256, 256)])
    assert w["decode"] == (4 * 32 * 64 * 62 * 830 * 4,
                           (2048 * 62 * 830 + 2 * 32 * 64 * 2 * 62) * 4)
    assert 0.4e9 < w["decode"][1] < 0.45e9        # ISSUE: ~0.4 GB a tick
    pairs = 256 * 256 + 256 * 257 // 2
    assert w["prefill"] == (4 * 32 * 64 * pairs * 4,
                            (2048 * 512 + 2 * 32 * 64 * 2 * 256) * 4)
    # nothing to read gives nothing, never 0
    assert k_ssm.classes({"cfg": cfg}) == {} and k_pa.classes({"cfg": cfg}) == {}


def test_the_state_carry_share_reads_nothing_where_the_program_has_no_counter():
    """The parent's engine has no `carried_chunks`: the reader leaves the
    metric out; with it, the share is the carried chunks over all chunks."""
    spec = _spec("state_carry_share.gen1k")
    reg = lambda n: {"llm_prefill_chunks_total": {"value": n}}  # noqa: E731
    parent = {"before": {"registry": reg(10), "stats": {}},
              "after": {"registry": reg(40), "stats": {}}}
    assert delta_ratio.read(spec, parent) is None
    key = "recurrent_state.carried_chunks"
    change = {"before": {"registry": reg(10), "stats": {key: 4}},
              "after": {"registry": reg(40), "stats": {key: 24}}}
    assert delta_ratio.read(spec, change) == pytest.approx(100 * 20 / 30)


def x_state(r):
    return r["extra"]["recurrent_state"]["bytes"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_granite_root(tmp_path_factory.mktemp("bench_granite"))


def test_the_tiny_root_passes_the_manifests_checks(root):
    manifest.check_manifest(manifest.load_manifest(root), root)


def test_a_sound_run_is_correct_and_reports_its_cells_metrics(root):
    r = run.run_cell(root, CELL, 2**31 + 7, 3.0, False, require_tpu=False,
                     clock0=time.perf_counter())
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 6
    assert set(r["metrics"]) == {"out_tokens_per_s", "setup_s"}
    assert all(v["value"] > 0 for v in r["metrics"].values())
    for held in ("mean_logit_gap", "p995_logit_gap"):
        value, limit = r["compared"][held]
        assert 0 <= value <= limit
    got, least = r["compared"]["recurrent_state_bytes"]
    # 4 slots x 6 mamba layers x (16 x 32 x 16 float32 + 3 x (512 + 2 x 16) bfloat16)
    assert least == ">=" + str(4 * 6 * (16 * 32 * 16 * 4 + 3 * 544 * 2))
    assert got == int(least[2:]) == x_state(r)
    x = r["extra"]
    assert x["cache_kinds"]["recurrent"]["layers"] == 6
    assert x["cache_kinds"]["paged_kv"]["layers"] == 2
    assert x["recurrent_state"]["carried_chunks"] > 0
    assert any(k.startswith("paged_") for k in x["attn_dispatch"])
    json.dumps(r)


def test_the_readers_find_the_counters_of_a_run(root):
    cell = manifest.load_cell(root, CELL)
    e2e, obs, check = serve_granite.run(cell, 9, 2.0, False, time.perf_counter(),
                                        lambda msg: None)
    assert check["correct"]
    obs.update(peak=manifest.peaks_for(cell["peaks"], "cpu"), chips=1)
    got = manifest.read_metrics(cell["per_layer"], obs)
    assert got["mfu.gen1k"]["value"] > 0
    assert got["decode_batch_mean.gen1k"]["value"] > 1
    # prompts of 16..60 tokens in chunks of 16: 1..4 chunks, most carried
    assert 30 < got["state_carry_share.gen1k"]["value"] < 80
    assert 0 < got["chunk_tick_share.gen1k"]["value"] <= 100
    assert "ssm_update_roofline.gen1k" not in got   # no trace: nothing, not 0
    assert "paged_attn_roofline.gen1k" not in got
    assert mfu_granite_hybrid.read({}, obs) == got["mfu.gen1k"]["value"]
    # the work functions over the whole window, as a traced part gives them
    obs.update(traced=obs["window"])
    assert k_ssm.classes(obs)["decode"][1] > 0
    assert k_pa.classes(obs)["decode"][1] > 0 and k_pa.classes(obs)["prefill"][1] > 0


def test_the_controls_are_read_beside_the_run_and_fp8_fails(root):
    """The configuration states bfloat16; computed with fp8 operands, the
    nearest precision below, the tokens it puts first lie further below the
    reference's best than a limit allows.  int8 is read beside it (at this
    size it lies near the sound runs; the cell's limits hold it on the chip)."""
    cell = manifest.load_cell(root, CELL)
    rng = np.random.default_rng(3)
    sample = [{"prompt": rng.integers(0, 256, n, dtype=np.int32), "max_new_tokens": 12,
               "tokens": rng.integers(0, 256, 12, dtype=np.int32)}
              for n in (60, 25, 33, 40, 50, 16)]
    limits = cell["job"]["limits"]
    out = serve_granite.compare(cell["config"], 3, sample, 128, limits,
                                controls=["fp8", "int8"])
    c = out["controls"]
    assert c["control_fp8_p995_logit_gap"] > limits["p995_logit_gap"] \
        or c["control_fp8_mean_logit_gap"] > limits["mean_logit_gap"]
    assert set(c) == {f"control_{q}_{s}_logit_gap" for q in ("fp8", "int8")
                      for s in ("mean", "widest", "p995")}
    assert all(np.isfinite(v) for v in c.values())


def _compare_with_gaps(monkeypatch, gaps, **kw):
    """`compare` at the REAL cell's limits over gaps given, not computed."""
    from benchmark.reference import granite_hybrid_ref
    monkeypatch.setattr(granite_hybrid_ref, "served_gap",
                        lambda *a: (np.asarray(gaps, np.float32), {}))
    job = manifest.load_cell(REPO, NAME)["job"]
    sample = [{"prompt": np.zeros(4, np.int32), "max_new_tokens": 2,
               "tokens": np.zeros(2, np.int32)}]
    return serve_granite.compare(real_cfg(), 1, sample, 1536, job["limits"], **kw)


@pytest.mark.parametrize("itemsize,correct", [(4, True), (2, False)])
def test_a_smaller_ssm_state_than_the_configuration_states_is_not_correct(
        monkeypatch, itemsize, correct):
    """The gaps cannot see the state's precision, so its bytes are held."""
    stated = serve_granite.state_bytes(real_cfg(), 64)
    held = 64 * 36 * (64 * 64 * 128 * itemsize + 3 * 4352 * 2)
    out = _compare_with_gaps(monkeypatch, np.zeros(64), held_state=(held, stated))
    assert out["correct"] is correct
    assert out["numbers"]["recurrent_state_bytes"] == [held, ">=4892000256"]


def test_a_sparse_fault_and_a_spread_one_are_both_not_correct(monkeypatch):
    limits = manifest.load_cell(REPO, NAME)["job"]["limits"]
    sound = np.zeros(2000)
    assert _compare_with_gaps(monkeypatch, sound)["correct"] is True
    sparse = sound.copy()
    sparse[::100] = 3 * limits["p995_logit_gap"]           # one token in 100
    out = _compare_with_gaps(monkeypatch, sparse)
    assert out["correct"] is False
    assert out["numbers"]["mean_logit_gap"][0] < limits["mean_logit_gap"]
    spread = np.full(2000, 1.5 * limits["mean_logit_gap"])  # every token a little
    assert spread[0] < limits["p995_logit_gap"]
    out = _compare_with_gaps(monkeypatch, spread)
    assert out["correct"] is False
    assert out["numbers"]["p995_logit_gap"][0] < limits["p995_logit_gap"]
