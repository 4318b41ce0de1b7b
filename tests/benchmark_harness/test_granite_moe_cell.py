"""The Granite-4.0-H-Small cell's files: the manifest with the appended
entries, the configuration against the catalog with its cuts, the runner end
to end on the CPU at a tiny size (sound run correct, the four controls read
beside it), the held state bytes, and the work functions and readers against
counts worked by hand."""
import json
import os
import time

import numpy as np
import pytest

from benchmark import flops_granite_moe as flops
from benchmark import manifest, run, serve_granite_moe
from benchmark import weights_granite_moe as W
from benchmark.kernels import moe_glu_experts_granite as k_moe
from benchmark.kernels import ssm_update_granite as k_ssm
from benchmark.readers import delta_ratio, mfu_granite_moe
from benchmark.reference import granite_moe_ref

from bh_tiny import REPO
from bh_tiny_granite_moe import CELL, make_granite_moe_root

NAME = "granite4h-small-gen1k-batch"
CONFIG = "granite-4.0-h-small-l10-ep2"
NEW_METRICS = ["moe_glu_experts_roofline.small1k", "ssm_update_roofline.small1k",
               "moe_pairs_per_expert.small1k", "mfu.small1k", "tick_mean_ms.small1k",
               "decode_batch_mean.small1k", "chunk_tick_share.small1k"]
CONTROLS = ("fp8", "int8", "softmax_all", "held_renorm")


def real_cfg():
    with open(os.path.join(REPO, "benchmark", "configs", CONFIG + ".json")) as f:
        return json.load(f)


def _spec(name):
    with open(os.path.join(REPO, "benchmark", "metrics", name + ".json")) as f:
        return json.load(f)


def test_the_manifest_checks_out_with_the_appended_entries():
    m = manifest.load_manifest(REPO)
    manifest.check_manifest(m, REPO)
    cell = manifest.load_cell(REPO, NAME)
    assert cell["cell"]["chips"] == 1 and cell["job"]["kind"] == "serve_granite_moe"
    assert cell["cell"]["config"] == CONFIG and cell["cell"]["traffic"] == "gen1k-batch"
    # Micro's cell's engine and traffic, so the two differ only in the experts
    micro = manifest.load_cell(REPO, "granite4h-micro-gen1k-batch")
    assert cell["job"]["engine"] == micro["job"]["engine"]
    assert cell["traffic"] == micro["traffic"]
    assert cell["job"]["check_pad_to"] == 1536 and cell["job"]["check_requests"] == 6
    assert {e["name"] for e in cell["end_to_end"]} == {"out_tokens_per_s", "setup_s"}
    assert {e["name"] for e, _ in cell["per_layer"]} == set(NEW_METRICS) | {"window_compiles"}
    # appended at the end of every list, nothing moved
    assert m["workloads"][-1]["name"] == NAME and m["configs"][-1]["name"] == CONFIG
    assert [x["name"] for x in m["per_layer"][-len(NEW_METRICS):]] == NEW_METRICS
    assert all(x["moves"] == "out_tokens_per_s" and x["workloads"] == [NAME]
               for x in m["per_layer"][-len(NEW_METRICS):])
    out_tps = next(e for e in m["end_to_end"] if e["name"] == "out_tokens_per_s")
    assert out_tps["workloads"][-2:] == ["granite4h-micro-gen1k-batch", NAME]
    assert m["configs"][-1]["reduced"] == ["num_hidden_layers", "layer_types",
                                           "num_local_experts", "vocab_size"]
    # the twins' specs, the cell's own tag apart
    for t in ("tick_mean_ms", "decode_batch_mean", "chunk_tick_share",
              "ssm_update_roofline"):
        assert _spec(t + ".small1k") == _spec(t + ".gen1k")
    assert _spec("moe_pairs_per_expert.small1k") == dict(
        _spec("moe_pairs_per_expert.doc16k"), scale=1 / 36)


#: the source's config.json (ibm-granite/granite-4.0-h-small), key for key
SOURCE = "https://huggingface.co/ibm-granite/granite-4.0-h-small/blob/main/config.json"
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.0078125,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 4096,
    "intermediate_size": 768,
    "layer_types": (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4,
    "logits_scaling": 16, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 128,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 10,
    "num_hidden_layers": 40, "num_key_value_heads": 8, "num_local_experts": 72,
    "position_embedding_type": "nope", "residual_multiplier": 0.22,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 1536, "tie_word_embeddings": True,
    "vocab_size": 100352}


def test_the_configuration_is_the_catalogs_with_its_cuts_listed():
    cfg = real_cfg()
    cut = {"num_hidden_layers", "layer_types", "num_local_experts", "vocab_size"}
    assert {k for k, v in PUBLISHED.items() if cfg.get(k) != v} == cut
    assert set(cfg["reduced"]) == cut == set(cfg["published"])
    assert cfg["published"] == {k: PUBLISHED[k] for k in cut}
    assert cfg["source"] == SOURCE
    assert cfg["layer_types"] == PUBLISHED["layer_types"][:10]
    assert cfg["share"] == {"experts_held": [0, 36], "router_experts": 72,
                            "vocab_ids": [0, 50176]}
    assert {"expert_width", "head_dim", "ssm_state", "weights", "eos"} <= set(cfg["assumed"])


def test_the_parameter_count_and_the_state_at_the_configurations_sizes():
    """Shapes only: nothing is allocated."""
    cfg = real_cfg()
    per = {k: sum(int(np.prod(s)) for s in W.layer_shapes(cfg, k).values())
           for k in ("mamba", "attention")}
    moe = 4096 * 72 + 3 * 36 * 768 * 4096 + 4096 * 3072 + 1536 * 4096 + 2 * 4096
    assert per["mamba"] == 4096 * 16768 + 4 * 8448 + 8448 + 3 * 128 + 8192 \
        + 8192 * 4096 + moe
    assert per["attention"] == 2 * 4096 * 4096 + 2 * 4096 * 1024 + moe
    n = W.n_params(cfg)
    assert n == 9 * per["mamba"] + per["attention"] + 50176 * 4096 + 4096
    assert round(n / 1e9, 3) == 4.757 and round(2 * n / 1e9, 2) == 9.51
    assert serve_granite_moe.state_bytes(cfg, 64) == 64 * 9 * (
        128 * 64 * 128 * 4 + 3 * 8448 * 2) == 2_445_115_392


def test_flops_and_kernel_work_against_sizes_worked_by_hand():
    cfg = real_cfg()
    rec = 5 * 128 * 64 * 128 + 4 * 8192
    m = 2 * 4096 * 16768 + 2 * 8192 * 4096 + 2 * 4 * 8448 + rec
    a = 4 * 4096 * (32 + 8) * 128
    shared = 6 * 4096 * 1536
    router = 2 * 4096 * 72
    assert flops.serve_flops(cfg, 1, 0, 0, 0) == 9 * m + a + 10 * (shared + router)
    assert flops.serve_flops(cfg, 0, 1, 0, 0) == 2 * 4096 * 50176   # the tied head
    assert flops.serve_flops(cfg, 0, 0, 1, 0) == 4 * 32 * 128       # one layer's key
    # the routed work is the COUNTED held pairs, 2 x 3 x 4096 x 768 each
    assert flops.serve_flops(cfg, 0, 0, 0, 1) == 2 * 3 * 4096 * 768
    # a decode tick of 62 rows: 10 layers, all 36 held experts fetched, 62 x
    # 10 x 10 / 2 pairs held
    pairs = 62 * 10 * 10 // 2
    f, b = k_moe.work(cfg, pairs, 360)
    assert f == 2 * pairs * 3 * 4096 * 768
    assert b == (360 * 3 * 4096 * 768 + 2 * pairs * 4096) * 2
    assert 6.7e9 < b < 6.9e9                     # ~6.79 GB a tick
    f, b = k_ssm.work(cfg, 62)
    assert f == 5 * 128 * 64 * 128 * 62 * 9
    assert b == (2 * 128 * 64 * 128 + 8192) * 4 * 62 * 9
    assert 4.6e9 < b < 4.8e9                     # 62 of 64 slots' 4.83 GB
    # nothing to read gives nothing, never 0
    assert k_moe.classes({"cfg": cfg}) == {} and k_ssm.classes({"cfg": cfg}) == {}


def test_the_expert_readers_read_nothing_where_the_program_has_no_counter():
    """An engine without the experts' counters gives the readers nothing:
    they leave the metrics out; with the counters, they read them."""
    spec = _spec("moe_pairs_per_expert.small1k")
    parent = {"before": {"registry": {}, "stats": {}},
              "after": {"registry": {}, "stats": {}}}
    assert delta_ratio.read(spec, parent) is None
    assert mfu_granite_moe.read({}, dict(parent, work=None)) is None
    change = {"before": {"stats": {"moe.decode.pairs_held": 100,
                                   "moe.decode.layer_calls": 10}},
              "after": {"stats": {"moe.decode.pairs_held": 3300,
                                  "moe.decode.layer_calls": 110}}}
    assert delta_ratio.read(spec, change) == pytest.approx(3200 / 100 / 36)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_granite_moe_root(tmp_path_factory.mktemp("bench_granite_moe"))


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
    # 4 slots x 3 mamba layers x (16 x 32 x 16 float32 + 3 x (512 + 2 x 16) bfloat16)
    assert least == ">=" + str(4 * 3 * (16 * 32 * 16 * 4 + 3 * 544 * 2))
    assert got == int(least[2:])
    x = r["extra"]
    assert x["cache_kinds"]["recurrent"]["layers"] == 3
    assert x["cache_kinds"]["paged_kv"]["layers"] == 1
    moe = x["moe"]
    # every layer reports, the paged one too: 4 layer calls a program call
    assert moe["decode"]["layer_calls"] % 4 == 0 and moe["decode"]["layer_calls"] > 0
    assert 0 < moe["decode"]["pairs_held"] and 0 < moe["decode"]["pairs_absent"]
    assert any(k.startswith("paged_") for k in x["attn_dispatch"])
    json.dumps(r)


def test_the_readers_find_the_counters_of_a_run(root):
    cell = manifest.load_cell(root, CELL)
    e2e, obs, check = serve_granite_moe.run(cell, 9, 2.0, False, time.perf_counter(),
                                            lambda msg: None)
    assert check["correct"]
    obs.update(peak=manifest.peaks_for(cell["peaks"], "cpu"), chips=1)
    got = manifest.read_metrics(cell["per_layer"], obs)
    assert got["mfu.small1k"]["value"] > 0
    assert got["decode_batch_mean.small1k"]["value"] > 1
    assert 0 < got["chunk_tick_share.small1k"]["value"] <= 100
    # a decode row sends 2 pairs a layer, about half of them to the 4 held
    # experts; the tiny cell's scale is the real one's 1/36, not 1/4
    pairs = got["moe_pairs_per_expert.small1k"]["value"] * 36 / 4
    assert 0 < pairs <= 4 * 2
    assert "moe_glu_experts_roofline.small1k" not in got   # no trace: nothing
    assert "ssm_update_roofline.small1k" not in got
    assert mfu_granite_moe.read({}, obs) == got["mfu.small1k"]["value"]
    # the work functions over the whole window, as a traced part gives them
    obs.update(traced=obs["window"],
               traced_counters={"before": obs["before"], "after": obs["after"]})
    assert k_ssm.classes(obs)["decode"][1] > 0
    moe = k_moe.classes(obs)
    assert moe["decode"][0] > 0 and moe["prefill"][0] > 0


def test_the_four_controls_are_read_beside_the_run_and_fp8_fails(root):
    """The configuration states bfloat16; computed with fp8 operands, the
    nearest precision below, the tokens it puts first lie further below the
    reference's best than a limit allows.  int8 and the two wrong gate forms
    (softmax over all the router's logits; renormalised over the held
    experts) are read beside it: at this size the experts' part is too small
    a share of the residual stream to move a token, so they lie near the
    sound runs, though each moves the hidden states (below); the cell's
    limits hold all four on the chip."""
    cell = manifest.load_cell(root, CELL)
    rng = np.random.default_rng(3)
    sample = [{"prompt": rng.integers(0, 256, n, dtype=np.int32), "max_new_tokens": 12,
               "tokens": rng.integers(0, 256, 12, dtype=np.int32)}
              for n in (60, 25, 33, 40, 50, 16)]
    limits = cell["job"]["limits"]
    out = serve_granite_moe.compare(cell["config"], 3, sample, 128, limits,
                                    controls=list(CONTROLS))
    c = out["controls"]
    assert c["control_fp8_p995_logit_gap"] > limits["p995_logit_gap"] \
        or c["control_fp8_mean_logit_gap"] > limits["mean_logit_gap"]
    assert set(c) == {f"control_{q}_{s}_logit_gap" for q in CONTROLS
                      for s in ("mean", "widest", "p995")}
    assert all(np.isfinite(v) for v in c.values())


@pytest.mark.parametrize("control", CONTROLS)
def test_each_control_moves_the_reference_hidden_states(root, control):
    cfg = manifest.load_cell(root, CELL)["config"]
    toks = np.random.default_rng(4).integers(0, 256, (2, 32), dtype=np.int32)
    sound = np.asarray(granite_moe_ref.hidden_states(cfg, 3, toks))
    low = np.asarray(granite_moe_ref.hidden_states(cfg, 3, toks, control))
    assert np.abs(low - sound).max() > 1e-3 * np.abs(sound).max()


def _compare_with_gaps(monkeypatch, gaps, **kw):
    """`compare` at the REAL cell's limits over gaps given, not computed."""
    monkeypatch.setattr(granite_moe_ref, "served_gap",
                        lambda *a: (np.asarray(gaps, np.float32), {}))
    job = manifest.load_cell(REPO, NAME)["job"]
    sample = [{"prompt": np.zeros(4, np.int32), "max_new_tokens": 2,
               "tokens": np.zeros(2, np.int32)}]
    return serve_granite_moe.compare(real_cfg(), 1, sample, 1536, job["limits"], **kw)


@pytest.mark.parametrize("itemsize,correct", [(4, True), (2, False)])
def test_a_smaller_ssm_state_than_the_configuration_states_is_not_correct(
        monkeypatch, itemsize, correct):
    stated = serve_granite_moe.state_bytes(real_cfg(), 64)
    held = 64 * 9 * (128 * 64 * 128 * itemsize + 3 * 8448 * 2)
    out = _compare_with_gaps(monkeypatch, np.zeros(64), held_state=(held, stated))
    assert out["correct"] is correct
    assert out["numbers"]["recurrent_state_bytes"] == [held, ">=2445115392"]


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
