"""ERNIE b512xs128 step-time breakdown via ablation (round-4 verdict #1).

Where do the ~700 ms of the ERNIE pretrain step go?  Times the compiled
TrainStep under a ladder of ablations (dropout off, heads off, forward
only) plus targeted microbenches (threefry vs rbg RNG, embedding-bwd
scatter); every window ends in a real host sync.

Run:  python tools/ernie_breakdown.py            # prints a JSON dict
"""
from __future__ import annotations

import dataclasses
import json
import sys
import time

import numpy as np

sys.path.insert(0, __file__.rsplit("/", 2)[0])

BATCH, SEQ, STEPS, WINDOWS = 512, 128, 8, 3


def _time_step(step_call, sync):
    """Median-of-WINDOWS window time for STEPS chained dispatches."""
    for _ in range(2):
        step_call()
    sync()
    ws = []
    for _ in range(WINDOWS):
        t0 = time.perf_counter()
        for _ in range(STEPS):
            out = step_call()
        sync(out)
        ws.append(time.perf_counter() - t0)
    return sorted(ws)[WINDOWS // 2] / STEPS


def _batch(cfg):
    import paddle_tpu as paddle

    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (BATCH, SEQ)).astype(np.int32))
    seg = paddle.to_tensor((rng.rand(BATCH, SEQ) > 0.5).astype(np.int32))
    mlm = rng.randint(0, cfg.vocab_size, (BATCH, SEQ)).astype(np.int32)
    mlm[rng.rand(BATCH, SEQ) > 0.15] = -100
    nsp = rng.randint(0, 2, (BATCH, 1)).astype(np.int32)
    return ids, seg, paddle.to_tensor(mlm), paddle.to_tensor(nsp)


def _build(drop=True, attn_drop=True, heads=True):
    import paddle_tpu as paddle
    from paddle_tpu.models.bert import BertConfig, ErnieForPretraining

    cfg = BertConfig.base()
    if not drop:
        cfg = dataclasses.replace(cfg, hidden_dropout_prob=0.0)
    if not attn_drop:
        cfg = dataclasses.replace(cfg, attention_probs_dropout_prob=0.0)
    paddle.seed(0)
    model = ErnieForPretraining(cfg)
    model.bfloat16()
    if heads:
        def loss_fn(ids, seg, mlm_labels, nsp):
            loss, _ = model(ids, token_type_ids=seg, masked_lm_labels=mlm_labels,
                            next_sentence_label=nsp)
            return loss
    else:
        def loss_fn(ids, seg, mlm_labels, nsp):
            seq, _pooled = model.bert(ids, seg)
            return (seq.astype("float32") * seq.astype("float32")).mean()
    return cfg, model, loss_fn


def _variant_step(drop=True, attn_drop=True, heads=True):
    import paddle_tpu as paddle

    cfg, model, loss_fn = _build(drop, attn_drop, heads)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4, weight_decay=0.01,
                                 parameters=model.parameters())
    step = paddle.jit.TrainStep(model, loss_fn, opt)
    ids, seg, mlm, nsp = _batch(cfg)
    call = lambda: step(ids, seg, mlm, nsp)  # noqa: E731
    sync = lambda out=None: float(out.item()) if out is not None else float(call().item())  # noqa: E731
    return call, sync


def _variant_masked(n_pred=20):
    """Reference pretrain recipe: MLM head over masked positions only."""
    import paddle_tpu as paddle
    from paddle_tpu.models.bert import BertConfig, ErnieForPretraining

    cfg = BertConfig.base()
    paddle.seed(0)
    model = ErnieForPretraining(cfg)
    model.bfloat16()

    def loss_fn(ids, seg, pos, labels, nsp):
        loss, _ = model(ids, token_type_ids=seg, masked_lm_labels=labels,
                        next_sentence_label=nsp, masked_positions=pos)
        return loss

    opt = paddle.optimizer.AdamW(learning_rate=1e-4, weight_decay=0.01,
                                 parameters=model.parameters())
    step = paddle.jit.TrainStep(model, loss_fn, opt)
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (BATCH, SEQ)).astype(np.int32))
    seg = paddle.to_tensor((rng.rand(BATCH, SEQ) > 0.5).astype(np.int32))
    pos = paddle.to_tensor(
        np.stack([rng.choice(SEQ, n_pred, replace=False) for _ in range(BATCH)]).astype(np.int32))
    labels = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (BATCH, n_pred)).astype(np.int32))
    nsp = paddle.to_tensor(rng.randint(0, 2, (BATCH, 1)).astype(np.int32))
    call = lambda: step(ids, seg, pos, labels, nsp)  # noqa: E731
    sync = lambda out=None: float(out.item()) if out is not None else float(call().item())  # noqa: E731
    return call, sync


def _variant_fwd(drop=True, attn_drop=None, heads=True):
    """Forward loss only (no grad, no optimizer) — same dropout/RNG work."""
    import jax

    from paddle_tpu.autograd import tape
    from paddle_tpu.framework import random as _random
    from paddle_tpu.tensor.tensor import Tensor

    if attn_drop is None:
        attn_drop = drop  # 'nodrop' means ALL dropout off, as in _variant_step
    cfg, model, loss_fn = _build(drop, attn_drop, heads)
    params, buffers = model.functional_state()
    ids, seg, mlm, nsp = _batch(cfg)
    raw = tuple(t._value for t in (ids, seg, mlm, nsp))

    def fwd(params, buffers, key, *batch):
        with _random.rng_key_scope(key):
            restore = model.bind_functional_state(params, buffers)
            try:
                with tape.no_grad():
                    args = tuple(Tensor(b, stop_gradient=True) for b in batch)
                    out = loss_fn(*args)
            finally:
                restore()
        loss = out[0] if isinstance(out, (tuple, list)) else out
        return loss._value

    jfwd = jax.jit(fwd)

    def call():
        key = _random.get_rng_key()
        return jfwd(params, buffers, key, *raw)

    sync = lambda out=None: float(np.asarray(out if out is not None else call()))  # noqa: E731
    return call, sync


def _rng_microbench(impl):
    """Cost of ONE step's worth of dropout mask generation: 25 hidden-size
    draws ([B*S, H]) + 12 attention-probs draws ([B, 12, S, S])."""
    import jax
    import jax.numpy as jnp

    key = jax.random.key(0, impl=impl)

    @jax.jit
    def draws(key):
        acc = jnp.zeros((), jnp.float32)
        for i in range(25):
            key, sub = jax.random.split(key)
            m = jax.random.bernoulli(sub, 0.9, (BATCH * SEQ, 768))
            acc = acc + jnp.sum(m[:1, :8].astype(jnp.float32))
        for i in range(12):
            key, sub = jax.random.split(key)
            m = jax.random.bernoulli(sub, 0.9, (BATCH, 12, SEQ, SEQ))
            acc = acc + jnp.sum(m[:1, :1, :1, :8].astype(jnp.float32))
        return acc

    call = lambda: draws(key)  # noqa: E731
    sync = lambda out=None: float(np.asarray(out if out is not None else call()))  # noqa: E731
    return _time_step(call, sync)


def _embed_bwd_microbench():
    """Embedding fwd+bwd in isolation: gather + scatter-add grads for the
    word/position/token-type tables at the bench shapes."""
    import jax
    import jax.numpy as jnp

    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, 30522, (BATCH, SEQ)), jnp.int32)
    pos = jnp.asarray(np.tile(np.arange(SEQ, dtype=np.int32), (BATCH, 1)))
    seg = jnp.asarray(rng.randint(0, 2, (BATCH, SEQ)), jnp.int32)
    w = jnp.asarray(rng.randn(30522, 768) * 0.01, jnp.bfloat16)
    wp = jnp.asarray(rng.randn(512, 768) * 0.01, jnp.bfloat16)
    wt = jnp.asarray(rng.randn(2, 768) * 0.01, jnp.bfloat16)

    def loss(w, wp, wt):
        e = jnp.take(w, ids, axis=0) + jnp.take(wp, pos, axis=0) + jnp.take(wt, seg, axis=0)
        return jnp.sum(e.astype(jnp.float32) * e.astype(jnp.float32))

    g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    call = lambda: g(w, wp, wt)  # noqa: E731
    sync = lambda out=None: float(np.asarray((out if out is not None else call())[0][0, 0]))  # noqa: E731
    return _time_step(call, sync)


def main():
    import jax

    out = {"platform": jax.devices()[0].platform, "batch_seq": [BATCH, SEQ]}

    def run(name, fn, *a, **kw):
        try:
            call, sync = fn(*a, **kw)
            out[f"step_ms_{name}"] = round(_time_step(call, sync) * 1e3, 1)
            print(f"# {name}: {out[f'step_ms_{name}']} ms", file=sys.stderr)
        except Exception as e:
            out[f"step_ms_{name}"] = None
            out[f"error_{name}"] = repr(e)[:160]
            print(f"# {name}: FAILED {repr(e)[:120]}", file=sys.stderr)

    run("masked", _variant_masked)
    run("full", _variant_step)
    run("nodrop", _variant_step, drop=False, attn_drop=False)
    run("noattndrop", _variant_step, attn_drop=False)
    run("encoder_only", _variant_step, heads=False)
    run("encoder_only_nodrop", _variant_step, heads=False, drop=False, attn_drop=False)
    run("fwd_only", _variant_fwd)
    run("fwd_only_nodrop", _variant_fwd, drop=False)

    out["rng_ms_threefry"] = round(_rng_microbench("threefry2x32") * 1e3, 1)
    out["rng_ms_rbg"] = round(_rng_microbench("rbg") * 1e3, 1)
    out["embed_bwd_ms"] = round(_embed_bwd_microbench() * 1e3, 1)

    print(json.dumps(out))


if __name__ == "__main__":
    main()
