"""The whole step's share of the chip's peak: operations the window's work
required (benchmark/flops.py, from shapes and the requests' own lengths) over
window x chips x peak bf16 FLOP/s.  {"reader": "mfu"}
"""
from benchmark import flops


def read(spec, obs):
    if obs["kind"] == "train":
        t = obs["traffic"]
        need = obs["steps"] * flops.train_flops_per_step(obs["cfg"], t["batch"], t["seq"])
    else:
        w = obs["work"](*obs["window"])
        need = flops.serve_flops(
            obs["cfg"], w["prefill_tokens"] + w["decode_tokens"],
            w["head_rows"], w["decode_ctx_sum"] + w["prefill_pairs"])
    if not need:
        return None
    return 100.0 * need / (obs["window_s"] * obs["chips"] * obs["peak"]["bf16_flops"])
