"""Continuous-batching LLM serving with paddle_tpu.inference.LLMEngine.

Run (tiny random weights, any backend; `chip_smoke.py` at the repo root is
the same engine at 7B widths on the attached TPU):

    JAX_PLATFORMS=cpu python examples/serve_llm_continuous.py

Demonstrates: slot-pool serving with one compiled decode step for every
in-flight request, chunked-prefill admission into a paged kv cache,
per-request sampling knobs, int8 pages (half footprint + half decode stream
via the Pallas decode kernel), and chunked multi-step scheduling for
high-latency hosts.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.inference import LLMEngine
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM


def main():
    paddle.seed(0)
    cfg = LlamaConfig.tiny(tensor_parallel=False, use_flash_attention=False,
                           max_position_embeddings=512)
    model = LlamaForCausalLM(cfg)
    model.eval()
    # production: model.bfloat16(); model.set_state_dict(paddle.load(ckpt))

    eng = LLMEngine(
        model,
        max_batch_slots=4,        # concurrent decode lanes
        max_seq_len=256,
        cache_dtype="int8",       # capacity + bandwidth lever
        decode_chunk=4,           # 4 tokens per compiled call
    ).start()                     # background pump; omit and call
    #                               eng.run_until_complete() for sync use

    rng = np.random.RandomState(0)
    try:
        futures = []
        for i in range(8):  # more requests than slots: the queue drains
            prompt = rng.randint(0, cfg.vocab_size, 10 + 7 * i).astype(np.int32)
            futures.append((i, eng.submit(
                prompt,
                max_new_tokens=16,
                do_sample=(i % 2 == 1),  # per-request sampling
                temperature=0.8,
                top_p=0.95,
            )))
        for i, fut in futures:
            print(f"request {i}: {fut.result(timeout=300)}")
    finally:
        eng.stop()


if __name__ == "__main__":
    main()
