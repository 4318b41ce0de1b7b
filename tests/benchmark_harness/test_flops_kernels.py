"""Operations and bytes from shapes, against values worked by hand."""
import json
import os

import pytest

from benchmark import flops, kernels, weights
from benchmark.kernels import flash_attention, paged_attention

from bh_tiny import REPO


def config(name):
    with open(os.path.join(REPO, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


MISTRAL = config("mistral-7b-v0.3-l16")
PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def test_parameter_counts():
    # a layer: q, o 4096^2 each; k, v 4096 x 1024 each; gate, up, down 4096 x 14336
    assert flops.layer_matmul_params(MISTRAL) == 2 * 4096**2 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert flops.layer_matmul_params(MISTRAL) == 218_103_808
    assert flops.head_params(MISTRAL) == 4096 * 32768
    # + 2 norms a layer, the final norm, embedding and head
    assert weights.n_params(MISTRAL) == 16 * (218_103_808 + 2 * 4096) + 4096 + 2 * 4096 * 32768
    assert weights.n_params(MISTRAL) == 3_758_231_552


def test_serve_flops_of_one_decode_token():
    # one token at context 1000 through 16 layers and the head
    got = flops.serve_flops(MISTRAL, tokens=1, head_rows=1, key_pairs=1000)
    want = 2 * 218_103_808 * 16 + 2 * 4096 * 32768 + 4 * 32 * 128 * 1000 * 16
    assert got == want == 7_509_901_312


def test_train_flops_per_step():
    cfg = dict(MISTRAL, num_hidden_layers=2)
    n = 2 * 218_103_808 + 4096 * 32768
    pairs = 4 * 2048 * 2049 // 2
    assert flops.train_flops_per_step(cfg, 4, 2048) == \
        6 * n * 8192 + 3 * 4 * 32 * 128 * pairs * 2


def test_paged_attention_work_by_hand():
    # decode: 32 tokens whose contexts sum to 32,000; one chunk of 256 at offset 512
    w = paged_attention.work(MISTRAL, 32, 32_000, [(512, 256)])
    f, b = w["decode"]
    assert f == 4 * 32 * 128 * 32_000 * 16
    assert b == (2 * 8 * 128 * 2 * 32_000 + 2 * 32 * 128 * 2 * 32) * 16
    f, b = w["prefill"]
    assert f == 4 * 32 * 128 * (256 * 512 + 256 * 257 // 2) * 16
    assert b == (2 * 8 * 128 * 2 * 768 + 2 * 32 * 128 * 2 * 256) * 16
    t, bound = kernels.least_seconds(w, PEAK)
    dec = max(w["decode"][0] / 197e12, w["decode"][1] / 819e9)
    pre = max(w["prefill"][0] / 197e12, w["prefill"][1] / 819e9)
    assert t == pytest.approx(dec + pre) and bound == "memory"
    assert dec == w["decode"][1] / 819e9          # decode is bound by bytes
    assert pre == w["prefill"][0] / 197e12        # a chunk by operations


def test_flash_attention_work_by_hand():
    cfg = config("mistral-7b-v0.3-l16")
    f, b = flash_attention.work(cfg, 4, 2048)["step"]
    pairs = 4 * 2048 * 2049 // 2
    assert f == 12 * 32 * 128 * pairs * 16
    q, kv = 4 * 2048 * 32 * 128 * 2, 4 * 2048 * 8 * 128 * 2
    assert b == (6 * q + 6 * kv) * 16
    t, bound = kernels.least_seconds({"step": (f, b)}, PEAK, chips=4)
    assert bound == "compute" and t == pytest.approx(f / (4 * 197e12))


def test_peaks_table_names_its_source():
    with open(os.path.join(REPO, "benchmark", "peaks.json")) as f:
        p = json.load(f)
    assert "Google Cloud" in p["source"]
    row = p["chips"][0]
    assert set(row["device_kinds"]) == {"TPU v5 lite", "TPU v5e"}
    assert (row["bf16_flops"], row["int8_ops"], row["hbm_bytes_per_s"], row["hbm_bytes"]) \
        == (197e12, 393e12, 819e9, 16e9)


@pytest.mark.parametrize("kind", ["serve", "train"])
def test_mfu_reader_by_hand(kind):
    from benchmark.readers import mfu

    cfg = dict(MISTRAL, num_hidden_layers=2)
    obs = {"kind": kind, "cfg": cfg, "window_s": 2.0, "chips": 1,
           "peak": {"bf16_flops": 197e12}}
    if kind == "train":
        obs.update(traffic={"batch": 4, "seq": 2048}, steps=3)
        need = 3 * flops.train_flops_per_step(cfg, 4, 2048)
    else:
        work = {"prefill_tokens": 256, "decode_tokens": 64.0, "head_rows": 65.0,
                "decode_ctx_sum": 64_000.0, "chunks": [(512, 256)],
                "prefill_pairs": 256 * 512 + 256 * 257 // 2}
        obs.update(window=(0.0, 2.0), work=lambda a, b: work)
        need = flops.serve_flops(cfg, 320, 65.0, 64_000 + 256 * 512 + 256 * 257 // 2)
    assert mfu.read({"reader": "mfu"}, obs) == pytest.approx(100 * need / (2.0 * 197e12))
    idle = dict(obs, steps=0, work=lambda a, b: dict.fromkeys(work, 0) if kind == "serve" else None)
    assert mfu.read({"reader": "mfu"}, idle) is None
