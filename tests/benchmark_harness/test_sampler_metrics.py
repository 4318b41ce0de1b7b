"""`sampler_sort_tick_share.*`: the share of ticks whose knobs made the fused
sampler sort the vocabulary, as data files for the `delta_ratio` reader over
`stats()["sampler"]`.  All three cells send greedy traffic, so each reads 0.0;
a program without the counter, as the parent commit is, reads nothing."""
import numpy as np
import pytest

from benchmark import manifest, serve
from benchmark.readers import delta_ratio

from bh_tiny import REPO, make_root

NEW = {"sampler_sort_tick_share.chat": ("mistral7b-chat-r80", "tpot_p95_ms"),
       "sampler_sort_tick_share.batch": ("mistral7b-fewshot-batch",
                                         "out_tokens_per_s"),
       "sampler_sort_tick_share.gen": ("nemotron3nano-gen-batch",
                                       "out_tokens_per_s")}


def test_the_manifest_takes_the_three_entries_wherever_they_stand():
    m = manifest.load_manifest(REPO)
    manifest.check_manifest(m, root=REPO)
    by_name = {x["name"]: x for x in m["per_layer"]}
    for name, (cell, moves) in NEW.items():
        assert by_name[name] == {
            "name": name, "unit": "%", "better": "lower",
            "source": "program_counter", "layer": "kernels", "moves": moves,
            "workloads": [cell]}
        specs = dict((e["name"], s)
                     for e, s in manifest.load_cell(REPO, cell)["per_layer"])
        assert specs[name] == {
            "reader": "delta_ratio", "scale": 100.0,
            "num": {"stats": "sampler.threshold_ticks"},
            "den": {"registry": "llm_decode_tick_duration_seconds",
                    "field": "count"}}
    # the hybrid cell's whole list: what PR 27 gave it and the one above
    assert {e["name"] for e, _ in manifest.load_cell(
        REPO, "nemotron3nano-gen-batch")["per_layer"]} == {
        "window_compiles", "tick_mean_ms.gen", "tick_host_ms.gen",
        "tick_sync_ms.gen", "decode_batch_mean.gen",
        "admit_blocked_slots_share.gen", "moe_pairs_per_expert.gen", "mfu.gen",
        "moe_experts_roofline.gen", "ssm_update_roofline.gen",
        "first_token_sync_ms.gen", "tick_stage_ms.gen", "tick_book_ms.gen",
        "sampler_sort_tick_share.gen"}


@pytest.fixture(scope="module")
def engine(tmp_path_factory):
    root = make_root(tmp_path_factory.mktemp("sampler"))
    cell = manifest.load_cell(root, "tiny-chat")
    _, eng, _ = serve.build_engine(cell["config"], cell["job"], 5, 64)
    eng.warmup()
    return eng


def _burst(eng, **knobs):
    """Two snapshots around a burst, taken as serve.run takes them."""
    snap = lambda: {"registry": serve.registry_snapshot(),  # noqa: E731
                    "stats": serve._flatten(eng.stats())}
    before = snap()
    rng = np.random.default_rng(0)
    futs = [eng.submit(rng.integers(0, 256, 20 + 9 * i, dtype=np.int32),
                       max_new_tokens=4 + i, **knobs) for i in range(6)]
    eng.run_until_complete()
    assert all(f.result(timeout=1) for f in futs)
    return {"before": before, "after": snap()}


@pytest.mark.parametrize("name", sorted(NEW))
def test_the_share_reads_zero_for_greedy_traffic_and_nothing_from_the_parent(
        name, engine):
    spec = manifest._load(f"{REPO}/benchmark/metrics/{name}.json")
    obs = _burst(engine)
    assert obs["after"]["stats"]["sampler.ticks"] \
        > obs["before"]["stats"]["sampler.ticks"]
    assert delta_ratio.read(spec, obs) == 0.0
    # a greedy request that carries thresholds sorts nothing either
    assert delta_ratio.read(spec, _burst(engine, top_p=0.9, top_k=5)) == 0.0
    stats = {k: v for k, v in obs["after"]["stats"].items()
             if not k.startswith("sampler.")}
    parent = {"before": obs["before"], "after": dict(obs["after"], stats=stats)}
    assert delta_ratio.read(spec, parent) is None
    assert name not in manifest.read_metrics(
        [({"name": name, "unit": "%"}, spec)], parent)


def test_the_share_counts_the_ticks_a_top_p_request_decodes(engine):
    spec = manifest._load(
        f"{REPO}/benchmark/metrics/sampler_sort_tick_share.gen.json")
    drawn = delta_ratio.read(spec, _burst(engine, do_sample=True))
    assert drawn == 0.0  # temperature only: a draw, no sort
    cut = delta_ratio.read(spec, _burst(engine, do_sample=True, top_p=0.9))
    assert 50.0 < cut <= 100.0  # every decode tick; admission-only ticks not
