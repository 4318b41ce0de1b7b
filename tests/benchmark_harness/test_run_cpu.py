"""The harness end to end on the CPU at a tiny size: no chip -> no result;
with the look for a chip skipped, a sound run is correct, a run with the
timed path broken underneath is not, and the lower-precision control is not."""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from benchmark import manifest, run, serve

from bh_tiny import REPO, make_root


def test_without_a_chip_the_command_prints_no_result():
    m = manifest.load_manifest(REPO)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(m["command"] + ["--workload", m["workloads"][0]["name"],
                                       "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs 1 TPU chip" in p.stderr and "cpu" in p.stderr


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench"))


def drive(root, cell, seed, seconds=2.0):
    return run.run_cell(root, cell, seed, seconds, False, require_tpu=False,
                        clock0=time.perf_counter())


@pytest.mark.parametrize("cell,metric", [("tiny-chat", "ttft_p95_ms"),
                                         ("tiny-batch", "out_tokens_per_s")])
def test_a_sound_run_is_correct_and_reports_its_cells_metrics(root, cell, metric):
    r = drive(root, cell, seed=2**31 + 11)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) >= {metric, "setup_s"}
    assert all(v["value"] > 0 for v in r["metrics"].values())
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] >= 1
    assert list(r)[-1] == "compared"
    value, limit = r["compared"]["widest_logit_gap"]
    assert 0 <= value <= limit
    assert r["compared"]["served_tokens_checked"][0] > 10
    json.dumps(r)


def test_a_token_altered_where_it_is_produced_is_not_correct(root, monkeypatch):
    from paddle_tpu.inference import llm_server

    orig = llm_server.LLMEngine._host_select

    def off_by_one(self, row, req):
        return (orig(self, row, req) + 1) % row.shape[-1]

    monkeypatch.setattr(llm_server.LLMEngine, "_host_select", off_by_one)
    r = drive(root, "tiny-chat", seed=5)
    assert r["correct"] is False
    value, limit = r["compared"]["widest_logit_gap"]
    assert value > limit


def test_a_request_cut_short_is_not_correct(root, monkeypatch):
    orig = serve._Recorder._done

    def drop_last(self, fut, rec):
        orig(self, fut, rec)
        if rec["tokens"] is not None and len(rec["tokens"]) > 2:
            rec["tokens"] = rec["tokens"][:-1]

    monkeypatch.setattr(serve._Recorder, "_done", drop_last)
    r = drive(root, "tiny-chat", seed=6)
    assert r["correct"] is False and r["compared"]["lengths_as_asked"][0] == 0


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 3])
def test_the_control_in_the_precision_below_is_not_correct(root, seed):
    """The configuration states bfloat16, so its control is fp8: the token the
    lower precision puts first lies further below the reference's best than
    the limit allows."""
    cell = manifest.load_cell(root, "tiny-chat")
    rng = np.random.default_rng(seed)
    sample = []
    for n in (150, 60, 30, 90):
        prompt = rng.integers(0, 256, n, dtype=np.int32)
        sample.append({"prompt": prompt, "max_new_tokens": 40,
                       "tokens": rng.integers(0, 256, 40, dtype=np.int32)})
    out = serve.compare(cell["config"], seed, sample, 256,
                        cell["job"]["limits"], quant="fp8")
    assert out["control_widest_logit_gap"] > cell["job"]["limits"]["widest_logit_gap"]


def test_ttft_from_admission_spans_sums_to_the_registrys_and_every_trace_is_kept(root):
    cell = manifest.load_cell(root, "tiny-chat")
    model, eng, tracer = serve.build_engine(cell["config"], cell["job"], 3, 64)
    eng.warmup()
    eng.start()
    try:
        before = serve.registry_snapshot()["llm_ttft_seconds"]
        rec = serve._Recorder(eng, "t")
        rng = np.random.default_rng(0)
        n = 24
        for i in range(n):
            rec.submit({"id": i, "prompt": rng.integers(0, 256, 20 + 5 * i, dtype=np.int32),
                        "max_new_tokens": 3 + i % 4, "prefix": None}, None)
        assert rec.wait_all(time.perf_counter() + 120)
        after = serve.registry_snapshot()["llm_ttft_seconds"]
    finally:
        eng.stop()
    assert len(tracer.store) == n                     # sample_every=1 keeps all
    for r in rec.records:
        serve._attach_spans(r, tracer.store)
    ttft = [r["first_token"] - r["submit"] for r in rec.records]
    assert all(t > 0 for t in ttft)
    assert after["count"] - before["count"] == n
    assert sum(ttft) == pytest.approx(after["sum"] - before["sum"], abs=2e-3 * n)
    assert all(r["queue_wait"] is not None and r["chunks"] for r in rec.records)
