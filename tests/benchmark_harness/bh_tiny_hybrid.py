"""bh_tiny's root with one more tiny cell: a Nemotron-H hybrid (pattern
MEM*E, 8 published experts of which 4 are held) under a closed loop, added as
files the way a later PR adds a cell."""
import os

from bh_tiny import REPO, _dump, _load, make_root

CELL, BASE = "tiny-gen", "nemotron3nano-gen-batch"
TINY = dict(hidden_size=64, vocab_size=256, num_hidden_layers=5,
            hybrid_override_pattern="MEM*E", num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, mamba_num_heads=4,
            mamba_head_dim=8, n_groups=2, ssm_state_size=16, chunk_size=8,
            n_routed_experts=4, num_experts_per_tok=2, moe_intermediate_size=32,
            moe_shared_expert_intermediate_size=48, torch_dtype="bfloat16",
            share={"experts_held": [0, 4], "router_experts": 8})
ENGINE = {"page_size": 16, "num_pages": 33, "prefill_chunk": 16,
          "max_seq_len": 128, "max_batch_slots": 4}
#: the MEAN gap: tiny bf16 runs read at most 8.4e-5 over 10 runs, the fp8
#: control at least 1.8e-3 over 5 (a bfloat16 SSM state alone reads 0 at this
#: size: scratch runs on the CPU, PR 27)
GAP_LIMIT = 3e-4
#: the 99.5th percentile: the same runs read at most 3.0e-3, the control at
#: least 2.6e-2
P995_LIMIT = 1e-2


def make_hybrid_root(root, gap_limit=GAP_LIMIT, p995_limit=P995_LIMIT):
    root = make_root(root)
    cfg = _load(os.path.join(REPO, "benchmark", "configs",
                             "nemotron-3-nano-30b-a3b-l16-ep2.json"))
    cfg.update(TINY)
    _dump(cfg, os.path.join(root, "benchmark", "configs", "tiny-hybrid.json"))
    m = _load(os.path.join(root, "BENCHMARK.json"))
    m["configs"].append({"name": "tiny-hybrid", "source": "test", "reduced": [],
                         "file": "benchmark/configs/tiny-hybrid.json", "why": "test"})
    job = _load(os.path.join(REPO, "benchmark", "workloads", BASE + ".json"))
    job.update(engine=ENGINE, check_pad_to=128, trace_seconds=1.0,
               limits={"mean_logit_gap": gap_limit, "p995_logit_gap": p995_limit})
    _dump(job, os.path.join(root, "benchmark", "workloads", CELL + ".json"))
    m["workloads"].append({"name": CELL, "config": "tiny-hybrid", "traffic": CELL,
                           "chips": 1, "why": "test"})
    for x in m["end_to_end"] + m["per_layer"]:
        if BASE in x.get("workloads", []):
            x["workloads"].append(CELL)
    _dump({"loop": "closed", "clients": 6, "schedule_seed": 1, "pool": 4096, "block": 16,
           "prompt": {"dist": "uniform", "min": 8, "max": 40},
           "output": {"dist": "uniform", "min": 4, "max": 12}},
          os.path.join(root, "benchmark", "traffic", CELL + ".json"))
    _dump(m, os.path.join(root, "BENCHMARK.json"))
    return root
