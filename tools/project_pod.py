"""v5e-256 pod projection from measured single-chip rates + validated
collective-traffic formulas.

The attached hardware is ONE v5e chip; the pod-scale north star
(BASELINE.md:22: >=70% MFU ERNIE-3.0 pretrain on v5e-256) can only be
addressed analytically.  Method:

1. ANALYTIC per-step collective bytes for each parallel axis (the same
   formulas Megatron/GSPMD cost models use).
2. VALIDATION: the same shapes are compiled on the 8-device virtual CPU
   mesh and the optimized HLO's actual collective bytes are counted
   (distributed/census.py); the formula must agree before it is trusted at
   256 chips (--validate).
3. PROJECTION: step time at v5e-256 = measured single-chip compute time
   (from BENCH_r*.json rates) + exposed collective time on public ICI
   specs, reported as both a no-overlap lower bound and a full-overlap
   upper bound.  Writes PROJECTION.md (--write).

Public v5e numbers used (Google Cloud TPU docs / jax-ml scaling book):
  - 197 TF/s bf16 per chip
  - ICI: 4 links/chip, ~45 GB/s one-way per link, 2D torus (16x16 at 256)
  - DCN only between slices (not needed <=256)
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

ICI_LINK_GBS = 45.0          # one-way per link, v5e
RING_AXIS_GBS = 2 * ICI_LINK_GBS   # bidirectional ring on one torus axis
PEAK_TFS = 197.0


# ---------------------------------------------------------------- formulas

def ring_allreduce_s(bytes_, n, axis_gbs=RING_AXIS_GBS):
    """Ring allreduce wall time over n chips on one torus axis."""
    if n <= 1 or bytes_ == 0:
        return 0.0
    return 2 * bytes_ * (n - 1) / n / (axis_gbs * 1e9)


def ring_reduce_scatter_s(bytes_, n, axis_gbs=RING_AXIS_GBS):
    if n <= 1 or bytes_ == 0:
        return 0.0
    return bytes_ * (n - 1) / n / (axis_gbs * 1e9)


ring_all_gather_s = ring_reduce_scatter_s


def torus_allreduce_s(bytes_, n):
    """2-phase allreduce on a 2D torus (16x16 for 256): reduce-scatter+
    allgather along x, then allreduce of the 1/nx shard along y."""
    import math

    nx = int(math.sqrt(n))
    if nx * nx != n or nx <= 1:
        return ring_allreduce_s(bytes_, n)
    t1 = ring_reduce_scatter_s(bytes_, nx) + ring_all_gather_s(bytes_, nx)
    t2 = ring_allreduce_s(bytes_ / nx, nx)
    return t1 + t2


# ------------------------------------------------- per-config traffic models

def dp_step_bytes(n_params, grad_bytes=2):
    """Pure data parallelism: ONE gradient allreduce per step (bf16)."""
    return {"allreduce": n_params * grad_bytes}


def tp_layer_bytes(batch, seq, hidden, act_bytes=2):
    """Megatron TP: per decoder layer, fwd 2 allreduces of the activations
    (attention out + mlp out) and bwd 2 more (ref mp_layers.py:95,171 —
    ColumnParallel f/RowParallel g operators)."""
    a = batch * seq * hidden * act_bytes
    return {"allreduce_per_layer": 4 * a}


def pp_microbatch_bytes(micro_batch, seq, hidden, act_bytes=2):
    """1F1B: one activation send fwd + one grad send bwd per microbatch per
    stage boundary (ppermute pairs)."""
    return {"ppermute_per_micro": 2 * micro_batch * seq * hidden * act_bytes}


def zero2_step_bytes(n_params_shard_group, grad_bytes=2, param_bytes=2):
    """ZeRO-2 over the dp axis: reduce-scatter grads + allgather updated
    params once per step (ref sharded_train_step.py)."""
    return {"reducescatter": n_params_shard_group * grad_bytes,
            "allgather": n_params_shard_group * param_bytes}


# --------------------------------------------------------------- projections

def project_ernie_dp256(bench):
    """Config #4 at pod scale: BERT/ERNIE-base pure DP over 256 chips."""
    n_params = bench.get("ernie_n_params", 125e6)
    tok_s = bench.get("ernie_tokens_per_sec_per_chip")
    mfu_chip = bench.get("ernie_mfu")
    if not tok_s:
        return None
    batch, seq = bench.get("ernie_batch_seq", [512, 128])
    t_compute = batch * seq / tok_s
    g = dp_step_bytes(int(n_params))["allreduce"]
    t_comm = torus_allreduce_s(g, 256)
    return {
        "config": "ERNIE/BERT-base MLM pretrain, DP=256 (v5e-256)",
        "per_chip_batch": batch, "seq": seq,
        "global_batch": batch * 256,
        "measured_chip_step_s": round(t_compute, 4),
        "allreduce_bytes_per_step": g,
        "ici_allreduce_s": round(t_comm, 4),
        "step_s_no_overlap": round(t_compute + t_comm, 4),
        "step_s_full_overlap": round(max(t_compute, t_comm), 4),
        "mfu_chip_measured": mfu_chip,
        "mfu_pod_no_overlap": round(mfu_chip * t_compute / (t_compute + t_comm), 4),
        "mfu_pod_full_overlap": round(mfu_chip * t_compute / max(t_compute, t_comm), 4),
        "tokens_per_sec_pod_no_overlap": round(batch * seq * 256 / (t_compute + t_comm), 0),
    }


def project_llama7b_hybrid256(bench, tp_cal=1.0):
    """Config #5 at pod scale: LLaMA-2-7B, tp=4 x pp=8 x dp(zero2)=8.
    tp_cal: measured census/formula calibration multiplier on the tp
    allreduce traffic (GSPMD moves embedding/logit terms beyond the
    Megatron-minimal per-layer count)."""
    tp, pp, dp = 4, 8, 8
    n_layers, hidden, seq = 32, 4096, 2048
    n_params = 6.74e9
    micro, n_micro = 1, 64  # dp-local batch 64 -> global 512; bubble 11%
    # per-chip compute rate: take the measured h=4096 single-chip MFU (the
    # same kernels/fusions run inside the tp/pp shard), fall back to 738M
    mfu_chip = bench.get("llama_h4096_mfu") or bench.get("llama_mfu", 0.6)
    chip_tfs = mfu_chip * PEAK_TFS
    tokens_local = micro * n_micro * seq
    flops_local = 6 * (n_params / (tp * pp)) * tokens_local \
        + 3 * 2 * micro * n_micro * seq * seq * hidden * (n_layers // pp)
    t_compute = flops_local / (chip_tfs * 1e12)
    # TP allreduces: per layer per microbatch, over the tp=4 ring (one axis),
    # scaled by the measured census/formula calibration
    tpb = tp_layer_bytes(micro, seq, hidden)["allreduce_per_layer"] * tp_cal
    t_tp = (n_layers // pp) * n_micro * ring_allreduce_s(tpb, tp)
    # PP: 2 boundary transfers per microbatch (one fwd, one bwd), pipeline
    # bubble (pp-1)/n_micro of the compute
    ppb = pp_microbatch_bytes(micro, seq, hidden)["ppermute_per_micro"]
    t_pp = n_micro * ppb / (ICI_LINK_GBS * 1e9)
    bubble = (pp - 1) / n_micro
    # ZeRO-2 over dp=8: reduce-scatter + allgather of this stage's params
    z = zero2_step_bytes(int(n_params / (tp * pp)))
    t_dp = ring_reduce_scatter_s(z["reducescatter"], dp) \
        + ring_all_gather_s(z["allgather"], dp)
    t_comm = t_tp + t_pp + t_dp
    t_no = t_compute * (1 + bubble) + t_comm
    t_full = max(t_compute * (1 + bubble), t_comm)
    flops_global = 6 * n_params * tokens_local * dp \
        + 3 * 2 * micro * n_micro * dp * seq * seq * hidden * n_layers
    return {
        "config": "LLaMA-2-7B, tp=4 x pp=8 x dp(zero2)=8 (v5e-256)",
        "microbatch": micro, "n_microbatch": n_micro,
        "global_batch": micro * n_micro * dp,
        "chip_tfs_assumed": round(chip_tfs, 1),
        "mfu_chip_measured": mfu_chip,
        "t_compute_s": round(t_compute, 4),
        "pipeline_bubble_frac": round(bubble, 4),
        "t_tp_allreduce_s": round(t_tp, 4),
        "t_pp_ppermute_s": round(t_pp, 4),
        "t_zero2_s": round(t_dp, 4),
        "step_s_no_overlap": round(t_no, 4),
        "step_s_full_overlap": round(t_full, 4),
        "mfu_pod_no_overlap": round(
            flops_global / (t_no * 256 * PEAK_TFS * 1e12), 4),
        "mfu_pod_full_overlap": round(
            flops_global / (t_full * 256 * PEAK_TFS * 1e12), 4),
    }


def project_serving_capacity(bench):
    """Serving-capacity axis (inference/llm_server.py): per-chip decode
    rates and kv-cache capacity from the newest bench round, plus the paged
    layout's capacity at the same HBM budget and the PREFIX-CACHE capacity
    on the shared-prefix fleet trace.  Paged/prefix numbers come from the
    round's kv_paged_* / kv_prefix_* / kv_tier_* fields when present; until
    a round measures them, they are derived with the same trace accounting
    bench.py uses (mixed lengths 100..L step 100 for paged; one shared
    system prompt + varied tails for prefix, page_size 128; host DRAM ~10x
    HBM for the hierarchical kv tiers) and labeled so."""
    from bench import paged_capacity_trace, shared_prefix_trace

    tok8 = bench.get("llama_decode_steady_tokens_per_sec")
    dense_b = bench.get("kv_bf16_max_batch")
    if not tok8 or not dense_b:
        return None
    L_ctx = bench.get("llama_decode_prompt_len", 1024) + 128
    L_pad = ((L_ctx + 127) // 128) * 128
    _, pages_mean = paged_capacity_trace(L_pad, 128)
    gain = L_pad / (pages_mean * 128)
    dense_b8 = bench.get("kv_int8_max_batch")
    measured = "kv_paged_max_batch" in bench
    paged_b = bench.get("kv_paged_max_batch", int(dense_b * gain))
    paged_b8 = bench.get("kv_paged_int8_max_batch",
                         int((dense_b8 or 0) * gain))
    # prefix cache on the shared-prefix trace: the SAME page budget the
    # paged numbers used (budget_pages ~= paged_b * mixed-trace pages/req),
    # charged only for each request's unique pages
    tr = shared_prefix_trace(L_pad, 128)
    measured_px = "kv_prefix_max_batch" in bench
    budget_pages = paged_b * pages_mean
    prefix_b = bench.get("kv_prefix_max_batch", int(
        (budget_pages - tr["shared_full_pages"]) // tr["unique_pages"]))
    prefix_b8 = bench.get("kv_prefix_int8_max_batch", int(
        (paged_b8 * pages_mean - tr["shared_full_pages"])
        // tr["unique_pages"]) if paged_b8 else None)
    tok32q = bench.get("llama_decode_int8_b32_steady_tokens_per_sec")
    out = {
        "config": f"LLM decode service, 738M model @ ctx {L_pad} "
                  "(per chip; x256 for the pod)",
        "decode_tokens_per_sec_chip_b8": tok8,
        "decode_tokens_per_sec_chip_b32": bench.get(
            "llama_decode_b32_steady_tokens_per_sec"),
        "decode_tokens_per_sec_chip_b32_int8": tok32q,
        "kv_dense_bf16_max_batch": dense_b,
        "kv_dense_int8_max_batch": dense_b8,
        "kv_paged_max_batch": paged_b,
        "kv_paged_int8_max_batch": paged_b8,
        "paged_capacity_gain_mixed_trace": round(gain, 2),
        "paged_numbers_source": "measured (bench kv_paged_*)" if measured
        else "derived from dense round via the bench.py trace formula",
        "kv_prefix_max_batch": prefix_b,
        "kv_prefix_int8_max_batch": prefix_b8,
        "prefix_capacity_gain_vs_paged": round(
            prefix_b / max(paged_b, 1), 2),
        "prefix_trace_hit_ratio": bench.get(
            "llm_prefix_cache_hit_ratio", tr["hit_ratio"]),
        "prefix_trace": {k: tr[k] for k in
                         ("shared_len", "tail_len", "new_tokens",
                          "total_pages", "unique_pages", "n_requests")},
        "prefix_numbers_source": "measured (bench kv_prefix_*)"
        if measured_px
        else "derived from the paged numbers via the bench.py shared-prefix"
             " trace formula",
    }
    # hierarchical kv tiers (host RAM + disk under the prefix cache): warm
    # prefixes survive HBM eviction in a host pool and re-enter via one
    # batched upload, so the WARM-SET capacity scales with host DRAM while
    # decode throughput is untouched (demotion runs off the tick path).
    # A v5e-class host hangs ~10x its per-chip HBM in DRAM off each chip,
    # so the derived fallback multiplies the HBM prefix budget by 11 (HBM
    # + 10x host); a measured round's kv_tier_* fields replace it.
    measured_tier = "kv_tier_capacity_multiplier" in bench
    dram_to_hbm = 10
    tier_mult = bench.get("kv_tier_capacity_multiplier", 1 + dram_to_hbm)
    out.update({
        "kv_tier_capacity_multiplier": tier_mult,
        "kv_tier_warm_prefix_pages": int(budget_pages * tier_mult),
        "kv_tier_warm_prefix_batch": int(
            (budget_pages * tier_mult - tr["shared_full_pages"])
            // tr["unique_pages"]),
        "kv_promote_us_per_page": bench.get("kv_promote_us_per_page"),
        "kv_promote_vs_reprefill_ratio": bench.get(
            "kv_promote_vs_reprefill_ratio"),
        "tier_numbers_source": "measured (bench kv_tier_*)" if measured_tier
        else f"derived: host DRAM ~{dram_to_hbm}x per-chip HBM, promotion "
             "latency unmeasured until a round runs _bench_kv_tiers",
    })
    if tok32q:
        out["pod_decode_tokens_per_sec_256chips_int8_b32"] = round(
            tok32q * 256, 0)
    return out


# --------------------------------------------------------------- validation

def validate_on_cpu_mesh():
    """Compile small-shape steps on the 8-device virtual mesh and compare
    the census-counted collective bytes against the SAME formulas used for
    the 256-chip projection.  Returns a list of {case, formula, census,
    ratio} dicts."""
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed.census import collective_census

    results = []

    # case 1: pure DP=8 — grad allreduce bytes == n_params * 4 (f32 grads
    # on CPU mesh; the formula's grad_bytes parameter)
    import paddle_tpu.nn as nn

    paddle.seed(0)
    net = nn.Sequential(nn.Linear(64, 128), nn.Tanh(), nn.Linear(128, 8))
    n_params = sum(int(np.prod(p.shape)) for p in net.parameters())
    mesh = dist.build_mesh(dp=8)
    opt = paddle.optimizer.SGD(learning_rate=0.1, parameters=net.parameters())
    mse = lambda x, y: paddle.mean((net(x) - y) ** 2)  # noqa: E731
    step = dist.ShardedTrainStep(net, mse, opt, mesh, zero_stage=0)
    x = paddle.to_tensor(np.random.randn(16, 64).astype(np.float32))
    y = paddle.to_tensor(np.random.randn(16, 8).astype(np.float32))
    step(x, y)
    census = step.compiled_stats(x, y)
    formula = dp_step_bytes(n_params, grad_bytes=4)["allreduce"]
    got = census["bytes_allreduce"]
    results.append({"case": "dp8_grad_allreduce", "formula": formula,
                    "census": got,
                    "ratio": round(got / max(formula, 1), 3)})

    # case 2+3: tp=2 Megatron decoder — the analytic model counts the 4
    # activation allreduces per layer; the GSPMD-partitioned step also moves
    # embedding/logit/loss terms, so the census exceeds the per-layer
    # formula.  Two sizes show the ratio converging toward the layer term as
    # layers/hidden grow; the LARGER config's ratio is exported as the
    # calibration multiplier the 7B projection applies to its tp traffic.
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    for tag, (h, inter, nl, vocab, B, S) in (
            ("tp2_tiny(h64,L2)", (64, 172, 2, 256, 8, 32)),
            ("tp2_mid(h256,L6)", (256, 688, 6, 512, 8, 64))):
        cfg = LlamaConfig(vocab_size=vocab, hidden_size=h,
                          intermediate_size=inter, num_hidden_layers=nl,
                          num_attention_heads=4, num_key_value_heads=4,
                          max_position_embeddings=S,
                          tensor_parallel=True, use_flash_attention=False)
        paddle.seed(0)
        model = LlamaForCausalLM(cfg)
        # mp-ONLY mesh (2 devices): isolates the tensor-parallel traffic —
        # with a dp axis present the census is dominated by the dp gradient
        # allreduce, which the projection models separately (zero2 terms)
        import jax as _jax

        mesh2 = dist.build_mesh(mp=2, devices=_jax.devices()[:2])
        opt2 = paddle.optimizer.SGD(learning_rate=0.1,
                                    parameters=model.parameters())

        def lm_loss(ids, labels, model=model):
            loss, _ = model(ids, labels=labels)
            return loss

        step2 = dist.ShardedTrainStep(model, lm_loss, opt2, mesh2,
                                      zero_stage=0)
        ids = paddle.to_tensor(np.random.randint(0, vocab, (B, S), np.int32))
        step2(ids, ids)
        census2 = step2.compiled_stats(ids, ids)
        formula2 = nl * tp_layer_bytes(B, S, h,
                                       act_bytes=4)["allreduce_per_layer"]
        got2 = census2["bytes_allreduce"]
        results.append({"case": f"{tag}_allreduce(layer-term formula)",
                        "formula": formula2, "census": got2,
                        "ratio": round(got2 / max(formula2, 1), 3)})
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--validate", action="store_true",
                    help="compile on the 8-device CPU mesh and compare the "
                         "census against the formulas")
    ap.add_argument("--write", action="store_true", help="write PROJECTION.md")
    args = ap.parse_args()

    if args.validate:
        # pin the virtual CPU mesh in-process (before jax initialises)
        os.environ.setdefault("XLA_FLAGS",
                              "--xla_force_host_platform_device_count=8")
        import jax

        jax.config.update("jax_platforms", "cpu")

    paths = sorted(glob.glob(os.path.join(ROOT, "BENCH_r*.json")))
    bench = {}
    if paths:
        with open(paths[-1]) as f:
            bench = json.load(f)
        bench = bench.get("parsed", bench)

    val = validate_on_cpu_mesh() if args.validate else None
    tp_cal = val[-1]["ratio"] if val else 1.0
    proj = {
        "ici_model": {"link_gbs_oneway": ICI_LINK_GBS,
                      "ring_axis_gbs": RING_AXIS_GBS,
                      "topology": "2D torus 16x16 (v5e-256)"},
        "tp_traffic_calibration": tp_cal,
        "ernie_dp256": project_ernie_dp256(bench),
        "llama7b_hybrid256": project_llama7b_hybrid256(bench, tp_cal=tp_cal),
        "serving_capacity": project_serving_capacity(bench),
        "validation": val,
        "bench_source": os.path.basename(paths[-1]) if paths else None,
        "roofline_source": _newest_roofline(),
    }
    print(json.dumps(proj, indent=1))
    if args.write:
        write_md(proj)
    return proj


def _newest_roofline():
    """Basename of the newest roofline residual round, or None (same
    lexical 'newest = last glob match' contract as the BENCH source;
    tools/docs_lint.py polices that PROJECTION.md cites it)."""
    paths = sorted(glob.glob(os.path.join(ROOT, "ROOFLINE_*.json")))
    return os.path.basename(paths[-1]) if paths else None


def write_md(proj):
    lines = ["# PROJECTION — v5e-256 pod-scale estimates",
             "",
             "Generated by `python tools/project_pod.py --validate --write`.",
             "Single-chip rates are MEASURED (from "
             f"`{proj['bench_source']}`); collective times are analytic on "
             "public v5e ICI specs; the traffic formulas are validated "
             "against the 8-device virtual mesh census below.",
             ""]
    if proj.get("roofline_source"):
        lines += [f"Per-op measured-vs-predicted attribution: "
                  f"`{proj['roofline_source']}` (the roofline residual "
                  f"plane's newest round; see `tools/roofline_report.py "
                  f"--diff` for the regression sentinel).",
                  ""]
    lines += [
             "## Interconnect model", "",
             f"- ICI one-way per link: {ICI_LINK_GBS} GB/s; bidirectional "
             f"ring per torus axis: {RING_AXIS_GBS} GB/s",
             "- v5e-256 topology: 2D torus 16x16; allreduce = 2-phase "
             "(reduce-scatter+allgather along x, allreduce shard along y)",
             ""]
    for key, title in (("ernie_dp256", "ERNIE/BERT-base DP-256 (north star)"),
                       ("llama7b_hybrid256", "LLaMA-2-7B tp4 x pp8 x zero2-dp8"),
                       ("serving_capacity",
                        "Serving capacity (paged kv cache)")):
        p = proj.get(key)
        if not p:
            continue
        lines += [f"## {title}", ""]
        for k, v in p.items():
            lines.append(f"- {k}: {v}")
        lines.append("")
    if proj.get("validation"):
        lines += ["## Formula validation (8-device virtual mesh census)", "",
                  "| case | formula bytes | census bytes | ratio |",
                  "|---|---|---|---|"]
        for r in proj["validation"]:
            lines.append(f"| {r['case']} | {r['formula']} | {r['census']} "
                         f"| {r['ratio']} |")
        lines.append("")
    with open(os.path.join(ROOT, "PROJECTION.md"), "w") as f:
        f.write("\n".join(lines))
    print("wrote PROJECTION.md")


if __name__ == "__main__":
    main()
