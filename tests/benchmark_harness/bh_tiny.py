"""A copy of the benchmark's DATA with tiny cells added as files: what a later
PR does.  The code stays where it is; only `root` changes."""
import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TINY = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
            num_key_value_heads=2, vocab_size=256, num_hidden_layers=2,
            torch_dtype="bfloat16")
ENGINE = {"page_size": 16, "num_pages": 65, "prefill_chunk": 32,
          "max_seq_len": 256, "max_batch_slots": 4}
CPU_PEAK = {"device_kinds": ["cpu"], "bf16_flops": 1e12, "int8_ops": 1e12,
            "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10, "ici_bits_per_s": 1e9}


def _dump(obj, path):
    with open(path, "w") as f:
        json.dump(obj, f)


def _load(path):
    with open(path) as f:
        return json.load(f)


#: set as the real limits are: tiny bf16 runs read at most 0.002 over 4 seeds,
#: the fp8 control at least 0.011 (scratch run on the CPU, PR 24)
GAP_LIMIT = 5e-3


def make_root(root, gap_limit=GAP_LIMIT):
    """BENCHMARK.json + benchmark/{configs,workloads,traffic,metrics,peaks.json}
    under `root`, with the cells tiny-chat and tiny-batch added as files."""
    root = str(root)
    for d in ("configs", "workloads", "traffic", "metrics"):
        shutil.copytree(os.path.join(REPO, "benchmark", d),
                        os.path.join(root, "benchmark", d))
    peaks = _load(os.path.join(REPO, "benchmark", "peaks.json"))
    peaks["chips"].append(CPU_PEAK)
    _dump(peaks, os.path.join(root, "benchmark", "peaks.json"))
    m = _load(os.path.join(REPO, "BENCHMARK.json"))
    cfg = _load(os.path.join(REPO, "benchmark", "configs", "mistral-7b-v0.3-l16.json"))
    cfg.update(TINY)
    _dump(cfg, os.path.join(root, "benchmark", "configs", "tiny.json"))
    m["configs"].append({"name": "tiny", "source": "test", "reduced": [],
                         "file": "benchmark/configs/tiny.json", "why": "test"})
    for cell, base in (("tiny-chat", "mistral7b-chat-r80"),
                       ("tiny-batch", "mistral7b-fewshot-batch")):
        job = _load(os.path.join(REPO, "benchmark", "workloads", base + ".json"))
        job.update(engine=ENGINE, check_pad_to=256, trace_seconds=1.0,
                   limits={"widest_logit_gap": gap_limit})
        _dump(job, os.path.join(root, "benchmark", "workloads", cell + ".json"))
        m["workloads"].append({"name": cell, "config": "tiny", "traffic": cell,
                               "chips": 1, "why": "test"})
        for x in m["end_to_end"] + m["per_layer"]:
            if base in x.get("workloads", []):
                x["workloads"].append(cell)
    _dump({"loop": "open", "rate_rps": 6.0, "schedule_seed": 1,
           "prompt": {"dist": "lognormal", "median": 40, "sigma": 0.8, "min": 4, "max": 150},
           "output": {"dist": "lognormal", "median": 10, "sigma": 0.5, "min": 2, "max": 40}},
          os.path.join(root, "benchmark", "traffic", "tiny-chat.json"))
    _dump({"loop": "closed", "clients": 8, "schedule_seed": 1, "pool": 8192, "block": 16,
           "prefixes": {"count": 3, "tokens": 48},
           "prompt": {"dist": "uniform", "min": 4, "max": 20},
           "output": {"dist": "uniform", "min": 2, "max": 8}},
          os.path.join(root, "benchmark", "traffic", "tiny-batch.json"))
    _dump(m, os.path.join(root, "BENCHMARK.json"))
    return root
