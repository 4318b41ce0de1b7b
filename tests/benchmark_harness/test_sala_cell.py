"""The MiniCPM-SALA cell's files: the manifest with the new entries, the
configuration against the catalog, the runner end to end on the CPU at a tiny
size (sound run correct, controls read beside it), the held numbers, and the
work functions and readers against counts worked by hand."""
import json
import os
import time

import numpy as np
import pytest

from benchmark import flops_minicpm_sala, manifest, run, serve_sala
from benchmark import weights_minicpm_sala as W
from benchmark.kernels import lightning_update as k_la
from benchmark.kernels import sparse_paged_attention as k_sa
from benchmark.readers import mfu_minicpm_sala

from bh_tiny import REPO
from bh_tiny_sala import CELL, make_sala_root

NAME = "minicpm-sala-docqa-batch"
NEW_METRICS = {
    "tick_mean_ms.docqa", "tick_host_ms.docqa", "tick_sync_ms.docqa",
    "tick_stage_ms.docqa", "tick_book_ms.docqa", "first_token_sync_ms.docqa",
    "decode_batch_mean.docqa", "admit_blocked_slots_share.docqa",
    "prefix_hit_share.docqa", "sparse_kv_read_share.docqa", "mfu.docqa",
    "sparse_attn_roofline.docqa", "lightning_update_roofline.docqa"}


def real_cfg():
    with open(os.path.join(REPO, "benchmark", "configs", "minicpm-sala-9b-l16.json")) as f:
        return json.load(f)


def test_the_manifest_checks_out_with_the_new_entries():
    m = manifest.load_manifest(REPO)
    manifest.check_manifest(m, REPO)
    cell = manifest.load_cell(REPO, NAME)
    assert cell["cell"]["chips"] == 1 and cell["job"]["kind"] == "serve_sala"
    assert cell["cell"]["traffic"] == "docqa-batch"
    assert cell["job"]["engine"] == {
        "max_batch_slots": 64, "max_seq_len": 33536, "page_size": 128,
        "num_pages": 1681, "prefill_chunk": 256, "prefix_cache": True,
        "state_checkpoints": 8}
    assert cell["traffic"] == {
        "loop": "closed", "clients": 96, "pool": 1024, "block": 64, "schedule_seed": 31,
        "prefixes": {"count": 4, "tokens": 32768},
        "prompt": {"dist": "uniform", "min": 64, "max": 256},
        "output": {"dist": "uniform", "min": 128, "max": 384}}
    # 4 documents + 64 x 640 own tokens + a quarter spare, and the trash page
    assert cell["job"]["engine"]["num_pages"] == (4 * 256 + 64 * 5) * 5 // 4 + 1
    assert cell["job"]["engine"]["max_seq_len"] >= 32768 + 256 + 384 + 1
    assert {e["name"] for e in cell["end_to_end"]} == {"out_tokens_per_s", "setup_s"}
    assert {e["name"] for e, _ in cell["per_layer"]} == NEW_METRICS | {"window_compiles"}
    assert all(e["moves"] == "out_tokens_per_s" and e["workloads"] == [NAME]
               for e, _ in cell["per_layer"] if e["name"] in NEW_METRICS)
    entry = next(c for c in m["configs"] if c["name"] == cell["cell"]["config"])
    assert entry["reduced"] == ["num_hidden_layers", "mixer_types"]
    assert [w["name"] for w in m["workloads"]][-1] == NAME     # appended, nothing moved
    assert [x["name"] for x in m["per_layer"]][-13:] == [
        e["name"] for e, _ in cell["per_layer"] if e["name"] in NEW_METRICS]


def test_the_configuration_keeps_every_published_width():
    cfg = real_cfg()
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "MiniCPM-SALA")
    differ = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert differ == set(cfg["reduced"]) == set(cfg["published"])
    assert all(cfg["published"][k] == row["config"][k] for k in differ)
    assert cfg["mixer_types"] == row["config"]["mixer_types"][9:25]
    assert cfg["mixer_types"].count("minicpm4") == 4 and cfg["source"] == row["source_url"]
    assert cfg["sparse_config"] == {"kernel_size": 32, "kernel_stride": 16, "block_size": 64,
                                    "topk": 64, "init_blocks": 1, "window_size": 2048,
                                    "dense_len": 8192}
    assert W.sizes(cfg)["residual"] == pytest.approx(1.4 / 32 ** 0.5)


def test_the_parameter_count_at_the_configurations_sizes_is_5_04_billion():
    """Shapes only: nothing is allocated."""
    cfg = real_cfg()
    per = {k: sum(int(np.prod(s)) for s in W.layer_shapes(cfg, k).values())
           for k in (W.LIGHTNING, W.SPARSE)}
    ffn = 3 * 4096 * 16384 + 2 * 4096
    assert per[W.LIGHTNING] == 5 * 4096 * 4096 + 2 * 128 + 4096 + ffn
    assert per[W.SPARSE] == 3 * 4096 * 4096 + 2 * 4096 * 256 + 2 * 128 + ffn
    n = W.n_params(cfg)
    assert n == 12 * per[W.LIGHTNING] + 4 * per[W.SPARSE] + 2 * 73448 * 4096 + 4096
    assert round(n / 1e9, 2) == 5.04 and round(2 * n / 1e9, 2) == 10.08
    assert serve_sala.state_bytes(cfg, 64) == 64 * 12 * 32 * 128 * 128 * 4 == 1_610_612_736
    assert serve_sala.state_bytes(cfg, 8) == 8 * 24 * 2 ** 20


def test_flops_and_kernel_work_against_sizes_worked_by_hand():
    cfg = real_cfg()
    light = 2 * (5 * 4096 * 4096 + 3 * 4096 * 16384)
    sparse = 2 * (3 * 4096 * 4096 + 2 * 4096 * 256 + 3 * 4096 * 16384)
    state = 5 * 32 * 128 * 128
    assert flops_minicpm_sala.serve_flops(cfg, 1, 0, 0, 0) == 12 * (light + state) + 4 * sparse
    assert flops_minicpm_sala.serve_flops(cfg, 0, 1, 0, 0) == 2 * 4096 * 73448
    assert flops_minicpm_sala.serve_flops(cfg, 0, 0, 1, 0) == 64 * 4 * 32 * 128
    assert flops_minicpm_sala.serve_flops(cfg, 0, 0, 0, 1) == 4 * 2 * 32 * 128
    # a decode tick of 62 rows at 33k: 64 blocks a row a layer, 4 layers
    f, b = k_sa.work(cfg, 62 * 4 * 64, 62 * 4)
    assert f == 4 * 32 * 128 * 62 * 4 * 64 * 64
    assert b == 62 * 4 * 64 * 64 * (2 * 2 * 128 * 2) + 62 * 4 * 2 * 32 * 128 * 2
    assert 1.0e9 < b < 1.1e9                       # ISSUE: ~1.2 GB with the compressed keys
    f, b = k_la.work(cfg, 62)
    assert f == 5 * 32 * 128 * 128 * 62 * 12
    assert b == (2 * 32 * 128 * 128 + 4 * 32 * 128) * 4 * 62 * 12
    assert 3.0e9 < b < 3.2e9                       # ISSUE: 3.1 GB a tick
    # nothing to read gives nothing, never 0: the reader leaves the metric out
    assert k_sa.classes({"cfg": cfg}) == {} and k_la.classes({"cfg": cfg}) == {}
    none = {"stats": {}}
    edges = {"traced_counters": {"before": none, "after": none}, "cfg": cfg}
    assert k_sa.classes(edges) == {} and k_la.classes(edges) == {}
    assert mfu_minicpm_sala.read({}, {"before": none, "after": none}) is None
    a = {"stats": {"sparse_attention.decode.selected_blocks": 640,
                   "sparse_attention.decode.layer_calls": 16}}
    got = {"cfg": cfg, "traced_counters": {"before": none, "after": a}}
    assert k_sa.classes(got) == {"decode": k_sa.work(cfg, 640, 16)}
    assert k_la.classes(got) == {"decode": k_la.work(cfg, 4.0)}   # 16 calls over 4 sparse layers


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_sala_root(tmp_path_factory.mktemp("bench_sala"))


def test_the_tiny_root_passes_the_manifests_checks(root):
    manifest.check_manifest(manifest.load_manifest(root), root)


def test_a_sound_run_is_correct_and_reports_its_cells_metrics(root):
    r = run.run_cell(root, CELL, 2**31 + 5, 3.0, False, require_tpu=False,
                     clock0=time.perf_counter())
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 6
    assert set(r["metrics"]) == {"out_tokens_per_s", "setup_s"}
    assert all(v["value"] > 0 for v in r["metrics"].values())
    for held in ("mean_logit_gap", "p995_logit_gap"):
        value, limit = r["compared"][held]
        assert 0 <= value <= limit
    assert r["compared"]["widest_logit_gap"][1] == "-"
    # every context served is past dense_len (24) and holds over topk (4) blocks
    assert r["compared"]["selected_blocks_per_query"] == [4.0, "4..4"]
    # 4 slots (3 checkpoints) x 2 lightning layers x 4 heads x 16 x 16 float32
    assert r["compared"]["recurrent_state_bytes"] == [32768, ">=32768"]
    assert r["compared"]["state_checkpoint_bytes"] == [24576, ">=24576"]
    x = r["extra"]
    assert x["cache_kinds"]["recurrent"]["layers"] == 2
    # every request of the window hit its document's checkpoint
    assert x["recurrent_state"]["checkpoints"]["restored"] == r["attempted"]
    assert x["prefix_cache"]["hit_tokens"] == 48 * r["attempted"]
    assert x["sparse_attention"]["decode"]["layer_calls"] > 0
    json.dumps(r)


def test_the_readers_find_the_counters_of_a_run(root):
    cell = manifest.load_cell(root, CELL)
    e2e, obs, check = serve_sala.run(cell, 9, 2.0, False, time.perf_counter(),
                                     lambda msg: None)
    assert check["correct"]
    obs.update(peak=manifest.peaks_for(cell["peaks"], "cpu"), chips=1)
    got = manifest.read_metrics(cell["per_layer"], obs)
    assert got["mfu.docqa"]["value"] > 0
    # documents of 48 tokens, questions of 4..30: 62..92% of prompt tokens hit
    assert 60 < got["prefix_hit_share.docqa"]["value"] < 95
    # 4 blocks of 13..22 in context
    assert 18 < got["sparse_kv_read_share.docqa"]["value"] < 31
    assert got["decode_batch_mean.docqa"]["value"] > 1
    assert "sparse_attn_roofline.docqa" not in got   # no trace: nothing, not 0
    assert set(got) == NEW_METRICS - {"sparse_attn_roofline.docqa",
                                      "lightning_update_roofline.docqa"} | {"window_compiles"}
    # the work functions over the whole window, as a traced part would give them
    obs.update(traced=obs["window"],
               traced_counters={"before": obs["before"], "after": obs["after"]})
    d = {k: obs["after"]["stats"][f"sparse_attention.decode.{k}"]
         - obs["before"]["stats"][f"sparse_attention.decode.{k}"]
         for k in ("selected_blocks", "layer_calls")}
    assert d["selected_blocks"] == 4 * d["layer_calls"] > 0
    assert k_sa.classes(obs)["decode"] == k_sa.work(cell["config"], d["selected_blocks"],
                                                    d["layer_calls"])
    assert k_la.classes(obs)["decode"] == k_la.work(cell["config"], d["layer_calls"] / 2)


def _sample():
    rng = np.random.default_rng(3)
    return [{"prompt": rng.integers(0, 256, n, dtype=np.int32), "max_new_tokens": 12,
             "tokens": rng.integers(0, 256, 12, dtype=np.int32)} for n in (60, 45, 77, 30)]


@pytest.mark.parametrize("control", ["fp8", "all_blocks", "forced_only", "bf16_state"])
def test_the_controls_are_read_beside_the_run(root, control):
    """The configuration states bfloat16, so its precision control is fp8: the
    tokens it puts first lie further below the reference's best than the
    limits allow.  The three variants of the mechanism (every block read, the
    forced blocks alone, a bfloat16 state) are computed and reported; what the
    gaps do not see of them is held by the numbers beside the gaps."""
    cell = manifest.load_cell(root, CELL)
    out = serve_sala.compare(cell["config"], 3, _sample(), 128, cell["job"]["limits"],
                             controls=[control])
    got = out["controls"]
    assert set(got) == {f"control_{control}_{s}_logit_gap" for s in ("mean", "p995", "widest")}
    assert all(np.isfinite(v) and v >= 0 for v in got.values())
    if control == "fp8":
        limits = cell["job"]["limits"]
        assert got["control_fp8_mean_logit_gap"] > limits["mean_logit_gap"]
        assert got["control_fp8_p995_logit_gap"] > limits["p995_logit_gap"]


def _compare_with_gaps(monkeypatch, gaps, held):
    """`compare` at the REAL cell's limits over gaps given, not computed."""
    from benchmark.reference import minicpm_sala_ref
    monkeypatch.setattr(minicpm_sala_ref, "served_gap",
                        lambda *a: (np.asarray(gaps, np.float32), {}))
    job = manifest.load_cell(REPO, NAME)["job"]
    sample = [{"prompt": np.zeros(4, np.int32), "max_new_tokens": 2,
               "tokens": np.zeros(2, np.int32)}]
    return serve_sala.compare(real_cfg(), 1, sample, 33536, job["limits"], held=held)


def _held(per_query=64.0, state=1_610_612_736, ckpt=201_326_592):
    return {"selected_blocks_per_query": (per_query, (64, 64)),
            "recurrent_state_bytes": (state, (1_610_612_736, None)),
            "state_checkpoint_bytes": (ckpt, (201_326_592, None))}


@pytest.mark.parametrize("held,correct", [
    (_held(), True),
    (_held(per_query=517.3), False),          # every block read: the dense pass
    (_held(per_query=34.0), False),           # the forced blocks alone
    (_held(per_query=63.98), False),          # one row in fifty short of a block
    (_held(state=805_306_368), False),        # a bfloat16 state
    (_held(ckpt=100_663_296), False),         # bfloat16 checkpoints
], ids=["sound", "all_blocks", "forced_only", "a_block_short", "bf16_state", "bf16_checkpoints"])
def test_what_the_gaps_cannot_see_is_held_beside_them(monkeypatch, held, correct):
    out = _compare_with_gaps(monkeypatch, np.zeros(64), held)
    assert out["correct"] is correct
    assert out["numbers"]["selected_blocks_per_query"][1] == "64..64"
    assert out["numbers"]["recurrent_state_bytes"][1] == ">=1610612736"


def test_the_held_numbers_come_from_the_counted_window():
    cfg = real_cfg()
    stats = {"recurrent_state": {"bytes": 1_610_612_736, "checkpoints": {
        "bytes": 201_326_592, "capacity": 8}}}
    before = {"sparse_attention.decode.selected_blocks": 1000,
              "sparse_attention.decode.layer_calls": 10}
    after = {"sparse_attention.decode.selected_blocks": 1000 + 64 * 248,
             "sparse_attention.decode.layer_calls": 10 + 248}
    served = [{"prompt": np.zeros(32832, np.int32), "tokens": np.zeros(200, np.int32)}]
    held = serve_sala.held_numbers(cfg, {"max_batch_slots": 64}, stats, (before, after), served)
    assert held == _held()
    # a context still inside dense_len reads every block: the range opens
    served.append({"prompt": np.zeros(100, np.int32), "tokens": np.zeros(3, np.int32)})
    assert serve_sala.held_numbers(cfg, {"max_batch_slots": 64}, stats, (before, after),
                                   served)["selected_blocks_per_query"][1] == (2, 64)
