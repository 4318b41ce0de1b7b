"""`chunk_tick_share.*`: the share of ticks on which every decoding row waits
behind a prefill chunk's weight pass, a data file for the `delta_ratio` reader
a cell.  The manifest checks out with them appended, each spec reads a
hand-made pair of snapshots to the share worked by hand, and a window in which
no tick ran reads nothing, not 0.

Two cells, not the five ISSUE 35 asked for: the accepted tests of the three
other cells (`test_sampler_metrics.py`, `test_sala_cell.py`,
`test_latent_cell.py`) hold the exact SET of their cell's per-layer names, and
those files are not this kind of PR's to edit.  The spec is the same file for
any cell: the last test reads it against the three that wait."""
import pytest

from benchmark import manifest
from benchmark.readers import delta_ratio

from bh_tiny import REPO

CELLS = [("chat", "mistral7b-chat-r80", "tpot_p95_ms"),
         ("batch", "mistral7b-fewshot-batch", "out_tokens_per_s")]
WAITING = ["nemotron3nano-gen-batch", "minicpm-sala-docqa-batch",
           "kanana2-docqa16k-batch"]


def snapshots(chunks, ticks, chunks0=7.0, ticks0=40):
    """Two snapshots as serve.run takes them: the registry's families summed
    over their series."""
    fams = lambda c, t: {  # noqa: E731
        "llm_prefill_chunks_total": {"value": c},
        "llm_decode_tick_duration_seconds": {"count": t, "sum": 0.02 * t}}
    return {"before": {"registry": fams(chunks0, ticks0), "stats": {}},
            "after": {"registry": fams(chunks0 + chunks, ticks0 + ticks),
                      "stats": {}}}


def test_the_manifest_checks_out_with_the_entries_appended():
    m = manifest.load_manifest(REPO)
    manifest.check_manifest(m, root=REPO)
    tail = m["per_layer"][-len(CELLS):]
    assert [e["name"] for e in tail] == [f"chunk_tick_share.{t}" for t, _, _ in CELLS]
    for entry, (_, cell, moves) in zip(tail, CELLS):
        assert entry == {"name": entry["name"], "unit": "%", "better": "lower",
                         "source": "program_counter",
                         "layer": "scheduler and admission", "moves": moves,
                         "workloads": [cell]}
    # no cell, no configuration, no end-to-end metric came with them
    assert len(m["workloads"]) == 5 and len(m["configs"]) == 4
    assert len(m["end_to_end"]) == 4


@pytest.mark.parametrize("tag,cell,moves", CELLS)
def test_a_cell_reads_its_share_and_nothing_from_a_window_with_no_tick(tag, cell, moves):
    name = f"chunk_tick_share.{tag}"
    per_layer = manifest.load_cell(REPO, cell)["per_layer"]
    (entry, spec), = [(e, s) for e, s in per_layer if e["name"] == name]
    assert spec["reader"] == "delta_ratio" and entry["moves"] == moves
    # no other cell's share is read in this cell
    assert [e["name"] for e, _ in per_layer
            if e["name"].startswith("chunk_tick_share.")] == [name]
    # 57 chunks on 150 ticks: 38 ticks in a hundred ran a chunk
    assert delta_ratio.read(spec, snapshots(57, 150)) == pytest.approx(38.0)
    assert delta_ratio.read(spec, snapshots(0, 150)) == 0.0
    assert delta_ratio.read(spec, snapshots(150, 150)) == pytest.approx(100.0)
    # a window with no tick: nothing, not 0 (and not a division by zero)
    assert delta_ratio.read(spec, snapshots(0, 0)) is None
    # a program without the counter (none such stands; the reader's contract)
    bare = snapshots(57, 150)
    del bare["after"]["registry"]["llm_prefill_chunks_total"]
    assert delta_ratio.read(spec, bare) is None
    got = manifest.read_metrics([(entry, spec)], snapshots(57, 150))
    assert got[name]["value"] == pytest.approx(38.0) and got[name]["unit"] == "%"


@pytest.mark.parametrize("cell", WAITING)
def test_the_cells_that_wait_are_untouched_and_the_spec_would_read_there(cell):
    """The three cells whose accepted tests hold their exact list keep it;
    the spec needs nothing of a cell (both counters are the engine's own)."""
    names = [e["name"] for e, _ in manifest.load_cell(REPO, cell)["per_layer"]]
    assert not [n for n in names if n.startswith("chunk_tick_share")]
    spec = manifest._load(f"{REPO}/benchmark/metrics/chunk_tick_share.batch.json")
    assert spec == manifest._load(f"{REPO}/benchmark/metrics/chunk_tick_share.chat.json")
    # 13 chunks on 50 ticks: the Nemotron cell's one tick in 3.8
    assert delta_ratio.read(spec, snapshots(13, 50)) == pytest.approx(26.0)
