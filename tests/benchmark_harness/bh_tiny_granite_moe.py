"""bh_tiny's root with one more tiny cell: a Granite-4.0-H decoder with routed
experts (layer types mamba, attention, mamba, mamba; 8 router experts of
which 4 are held, top 2; 64-wide heads, so the page pool packs two a row)
under a closed loop, added as files the way a later PR adds a cell."""
import os

from bh_tiny import REPO, _dump, _load, make_root

CELL, BASE = "tiny-small1k", "granite4h-small-gen1k-batch"
#: layers wide enough that their outputs, not the embedding, lead the
#: residual stream (see bh_tiny_granite)
TINY = dict(hidden_size=128, vocab_size=256, num_hidden_layers=4,
            layer_types=["mamba", "attention", "mamba", "mamba"],
            num_attention_heads=2, num_key_value_heads=2, mamba_n_heads=16,
            mamba_d_head=32, mamba_d_state=16, mamba_chunk_size=8,
            shared_intermediate_size=1024, intermediate_size=128,
            num_local_experts=4, num_experts_per_tok=2, torch_dtype="bfloat16",
            share={"experts_held": [0, 4], "router_experts": 8})
ENGINE = {"page_size": 16, "num_pages": 33, "prefill_chunk": 16,
          "max_seq_len": 128, "max_batch_slots": 4}
#: the MEAN gap: tiny bf16 runs read at most 9.1e-7 over 14 runs on 7 seeds,
#: the fp8 control at least 1.2e-5 over 3 (scratch runs on the CPU)
GAP_LIMIT = 4e-6
#: the 99.5th percentile: the same runs read at most 3.6e-5, the fp8 control
#: at least 2.4e-4
P995_LIMIT = 1.2e-4


def make_granite_moe_root(root, gap_limit=GAP_LIMIT, p995_limit=P995_LIMIT):
    root = make_root(root)
    cfg = _load(os.path.join(REPO, "benchmark", "configs",
                             "granite-4.0-h-small-l10-ep2.json"))
    cfg.update(TINY)
    _dump(cfg, os.path.join(root, "benchmark", "configs", "tiny-granite-moe.json"))
    m = _load(os.path.join(root, "BENCHMARK.json"))
    m["configs"].append({"name": "tiny-granite-moe", "source": "test", "reduced": [],
                         "file": "benchmark/configs/tiny-granite-moe.json", "why": "test"})
    job = _load(os.path.join(REPO, "benchmark", "workloads", BASE + ".json"))
    job.update(engine=ENGINE, check_pad_to=128, trace_seconds=1.0,
               limits={"mean_logit_gap": gap_limit, "p995_logit_gap": p995_limit})
    _dump(job, os.path.join(root, "benchmark", "workloads", CELL + ".json"))
    m["workloads"].append({"name": CELL, "config": "tiny-granite-moe", "traffic": CELL,
                           "chips": 1, "why": "test"})
    for x in m["end_to_end"] + m["per_layer"]:
        if BASE in x.get("workloads", []):
            x["workloads"].append(CELL)
    _dump({"loop": "closed", "clients": 6, "schedule_seed": 1, "pool": 4096, "block": 16,
           "prompt": {"dist": "uniform", "min": 16, "max": 60},
           "output": {"dist": "uniform", "min": 4, "max": 12}},
          os.path.join(root, "benchmark", "traffic", CELL + ".json"))
    _dump(m, os.path.join(root, "BENCHMARK.json"))
    return root
