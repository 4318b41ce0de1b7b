"""The per-layer metrics that read the pump's phase clock and its
admission-blocked counters are data files for the `delta_ratio` reader:
each resolves through the manifest, reads a number off two snapshots of a
tiny engine, and reads nothing (None, no error) off a program that has no
such counters, as the parent commit has not."""
import numpy as np
import pytest

from benchmark import manifest, serve
from benchmark.readers import delta_ratio

from bh_tiny import REPO, make_root

CHAT, BATCH = "mistral7b-chat-r80", "mistral7b-fewshot-batch"
NEW = [(f"{base}.{tag}", cell)
       for base in ("tick_host_ms", "tick_sync_ms", "tick_stage_ms",
                    "tick_book_ms", "first_token_sync_ms",
                    "admit_blocked_slots_share")
       for tag, cell in (("chat", CHAT), ("batch", BATCH))] \
    + [("admit_blocked_prefill_share.chat", CHAT)]


@pytest.fixture(scope="module")
def snapshots(tmp_path_factory):
    """Two snapshots around a burst on a tiny engine, taken as serve.run
    takes them."""
    root = make_root(tmp_path_factory.mktemp("tick"))
    cell = manifest.load_cell(root, "tiny-chat")
    model, eng, _ = serve.build_engine(cell["config"], cell["job"], 5, 64)
    snap = lambda: {"registry": serve.registry_snapshot(),  # noqa: E731
                    "stats": serve._flatten(eng.stats())}
    eng.warmup()
    before = snap()
    rng = np.random.default_rng(0)
    futs = [eng.submit(rng.integers(0, 256, 20 + 9 * i, dtype=np.int32),
                       max_new_tokens=4 + i) for i in range(8)]
    eng.run_until_complete()
    assert all(f.result(timeout=1) for f in futs)
    return {"before": before, "after": snap()}


def test_the_manifest_with_the_new_metrics_checks_out_against_its_files():
    m = manifest.load_manifest(REPO)
    manifest.check_manifest(m, root=REPO)
    names = [x["name"] for x in m["per_layer"]]
    assert sorted(names[-len(NEW):]) == sorted(n for n, _ in NEW)  # appended


@pytest.mark.parametrize("name,cell", NEW)
def test_a_tick_metric_reads_a_number_and_nothing_from_the_parent(
        name, cell, snapshots):
    entry, spec = next((e, s) for e, s in manifest.load_cell(REPO, cell)["per_layer"]
                       if e["name"] == name)
    assert spec["reader"] == "delta_ratio"
    assert entry["source"] == "program_counter" and entry["better"] == "lower"
    value = delta_ratio.read(spec, snapshots)
    assert value is not None and value >= 0.0
    if name.startswith(("tick_", "first_token")):
        assert 0.0 < value < 60_000.0  # milliseconds a tick
    else:
        assert value <= 100.0  # a share of the ticks
    stats = {k: v for k, v in snapshots["after"]["stats"].items()
             if not k.startswith(("tick_phases.", "admission_blocked."))}
    parent = {"before": snapshots["before"],
              "after": dict(snapshots["after"], stats=stats)}
    assert delta_ratio.read(spec, parent) is None


def test_the_tick_metrics_add_up_to_the_tick(snapshots):
    """host + the waits = the tick: what `tick_mean_ms` divides is what the
    phases split."""
    read = lambda n: delta_ratio.read(  # noqa: E731
        manifest._load(f"{REPO}/benchmark/metrics/{n}.json"), snapshots)
    s0, s1 = snapshots["before"]["stats"], snapshots["after"]["stats"]
    waits = sum(s1[k] - s0[k] for k in (
        "tick_phases.seconds.decode_sync", "tick_phases.seconds.first_token_sync"))
    ticks = snapshots["after"]["registry"]["llm_decode_tick_duration_seconds"]["count"] \
        - snapshots["before"]["registry"]["llm_decode_tick_duration_seconds"]["count"]
    whole = read("tick_host_ms.chat") + waits / ticks * 1000.0
    assert whole == pytest.approx(read("tick_mean_ms.chat"), rel=0.02)
    assert read("tick_sync_ms.chat") + read("tick_stage_ms.chat") \
        + read("tick_book_ms.chat") < whole
