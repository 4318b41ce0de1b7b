"""bh_tiny's root with one more tiny cell: a DeepSeek-V3-shaped decoder (one
dense and two expert layers, latent attention) under a closed loop with
shared documents of whole pages, added as files the way a later PR adds a
cell."""
import os

from bh_tiny import REPO, _dump, _load, make_root

CELL, BASE = "tiny-doc16k", "kanana2-docqa16k-batch"
TINY = dict(hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
            vocab_size=256, num_hidden_layers=3, num_attention_heads=4,
            num_key_value_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=8, qk_head_dim=24, v_head_dim=16, head_dim=8,
            n_routed_experts=8, num_experts_per_tok=2, rope_theta=10000,
            max_position_embeddings=512, published={"num_hidden_layers": 6},
            torch_dtype="float32")
ENGINE = {"page_size": 16, "num_pages": 65, "prefill_chunk": 16,
          "max_seq_len": 128, "max_batch_slots": 4, "prefix_cache": True}
#: the MEAN gap and the 99.5th percentile of ~35 served tokens.  The tiny cell
#: is float32: sound runs read 0 / 0 (every served token is the reference's
#: best) and the fp8 control at least 1e-3 / 1e-2 (scratch runs on the CPU,
#: PR 33), so a limit between them holds under load
GAP_LIMIT = 3e-4
P995_LIMIT = 3e-3


def make_latent_root(root, gap_limit=GAP_LIMIT, p995_limit=P995_LIMIT):
    root = make_root(root)
    cfg = _load(os.path.join(REPO, "benchmark", "configs", "kanana-2-30b-a3b-l8.json"))
    cfg.update(TINY)
    _dump(cfg, os.path.join(root, "benchmark", "configs", "tiny-latent.json"))
    m = _load(os.path.join(root, "BENCHMARK.json"))
    m["configs"].append({"name": "tiny-latent", "source": "test", "reduced": [],
                         "file": "benchmark/configs/tiny-latent.json", "why": "test"})
    job = _load(os.path.join(REPO, "benchmark", "workloads", BASE + ".json"))
    job.update(engine=ENGINE, check_pad_to=128, trace_seconds=1.0, check_requests=4,
               limits={"mean_logit_gap": gap_limit, "p995_logit_gap": p995_limit})
    _dump(job, os.path.join(root, "benchmark", "workloads", CELL + ".json"))
    m["workloads"].append({"name": CELL, "config": "tiny-latent", "traffic": CELL,
                           "chips": 1, "why": "test"})
    for x in m["end_to_end"] + m["per_layer"]:
        if BASE in x.get("workloads", []):
            x["workloads"].append(CELL)
    # documents of 3 whole pages, one chunk of own tokens a request
    _dump({"loop": "closed", "clients": 6, "schedule_seed": 1, "pool": 4096, "block": 16,
           "prefixes": {"count": 2, "tokens": 48},
           "prompt": {"dist": "uniform", "min": 4, "max": 16},
           "output": {"dist": "uniform", "min": 4, "max": 12}},
          os.path.join(root, "benchmark", "traffic", CELL + ".json"))
    _dump(m, os.path.join(root, "BENCHMARK.json"))
    return root
