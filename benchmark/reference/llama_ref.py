"""Plain float32 reference of the Llama-shaped decoder block, in jax.numpy.

RMSNorm, rotate-half RoPE (pairs i, i + D/2, as the sources' modelling code),
grouped-query causal attention, SwiGLU, untied head.  It imports nothing of
`paddle_tpu` and takes nothing the program made: weights come from
`benchmark.weights` by the seed, a layer at a time, so the whole model never
has to be resident.  Matrix products run at precision "highest"; on a TPU the
default would round float32 operands to bfloat16.

`quant` rounds every matmul operand (weights and activations) through a lower
precision first; it exists for the control that has to come out not correct.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights as W

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512  # query rows per block of attention: scores stay under ~1 GiB


def _q(x, quant):
    """Round through the control's precision; None is the reference itself."""
    if quant is None:
        return x
    if quant == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if quant == "fp8":
        # per-row absmax scaling to e4m3's range, as fp8 matmuls are fed
        s = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 448.0 + 1e-30
        return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    if quant == "int8":
        s = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0 + 1e-30
        return jnp.round(x / s) * s
    raise ValueError(f"unknown precision {quant!r}")


def _qw(w, quant):
    # weights: scaled per output column (the contraction runs over rows)
    return w if quant is None else _q(w.T, quant).T


def mm(x, w, quant=None):
    return jnp.matmul(_q(x, quant), _qw(w, quant), precision=HI)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, pos, theta):
    """x [T, H, D], pos [T]: rotate-half."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def attention(q, k, v, quant=None):
    """Causal GQA over one sequence.  q [T, H, D]; k, v [T, KV, D]."""
    T, H, D = q.shape
    rep = H // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    qb = min(Q_BLOCK, T)
    if T % qb:
        raise ValueError(f"sequence {T} is not a multiple of {qb}")
    kpos = jnp.arange(T)

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, 0)
        s = jnp.einsum("qhd,khd->hqk", _q(qi, quant), _q(k, quant),
                       precision=HI) / np.sqrt(D)
        qpos = i * qb + jnp.arange(qb)
        s = jnp.where(kpos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", _q(p, quant), _q(v, quant),
                          precision=HI)

    return jax.lax.map(block, jnp.arange(T // qb)).reshape(T, H, D)


def block(x, lw, cfg, quant=None):
    """One decoder layer over one sequence x [T, h]; lw: this layer's leaves
    by short name, float32."""
    s = W.sizes(cfg)
    T = x.shape[0]
    pos = jnp.arange(T)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    h = rms_norm(x, lw["ln1"], eps)
    q = rope(mm(h, lw["q"], quant).reshape(T, s["heads"], s["head_dim"]), pos, theta)
    k = rope(mm(h, lw["k"], quant).reshape(T, s["kv_heads"], s["head_dim"]), pos, theta)
    v = mm(h, lw["v"], quant).reshape(T, s["kv_heads"], s["head_dim"])
    a = attention(q, k, v, quant).reshape(T, s["h"])
    x = x + mm(a, lw["o"], quant)
    h = rms_norm(x, lw["ln2"], eps)
    return x + mm(jax.nn.silu(mm(h, lw["gate"], quant)) * mm(h, lw["up"], quant),
                  lw["down"], quant)


def _freeze(cfg):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool))))


@functools.partial(jax.jit, static_argnames=("cfg_items", "quant"))
def _layer_step(xs, key_data, i, cfg_items, quant):
    """Draw layer i's weights and apply it to every sequence of xs [n, T, h]."""
    cfg = dict(cfg_items)
    lw = {k: v.astype(jnp.float32)
          for k, v in W.make_layer(key_data, cfg, i).items()}
    return jax.lax.map(lambda x: block(x, lw, cfg, quant), xs)


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _embed(tokens, key_data, cfg_items):
    e = W.make_top(key_data, dict(cfg_items), ["embed"])["embed"].astype(jnp.float32)
    return e[tokens]


@functools.partial(jax.jit, static_argnames=("cfg_items", "quant"))
def _pick(xs, seq, pos, pick, key_data, cfg_items, quant):
    """Logits at rows (seq, pos) of xs: (best, logit of `pick`, argmax)."""
    cfg = dict(cfg_items)
    w = {k: v.astype(jnp.float32)
         for k, v in W.make_top(key_data, cfg, ["norm", "head"]).items()}
    lg = mm(rms_norm(xs[seq, pos], w["norm"], cfg["rms_norm_eps"]), w["head"], quant)
    at = jnp.take_along_axis(lg, pick[:, None], axis=-1)[:, 0]
    return jnp.max(lg, axis=-1), at, jnp.argmax(lg, axis=-1).astype(jnp.int32)


def hidden_states(cfg, seed, tokens, quant=None):
    """Final pre-norm hidden states [n, T, h] of `tokens` [n, T] (padding past
    a sequence's end is harmless: attention is causal), layer by layer."""
    key, items = W.seed_key(seed), _freeze(cfg)
    xs = _embed(jnp.asarray(tokens, jnp.int32), key, items)
    for i in range(cfg["num_hidden_layers"]):
        xs = _layer_step(xs, key, jnp.int32(i), items, quant)
    return xs


def full_logits(cfg, seed, tokens):
    """[n, T, V] float32: for the small sizes of the tests."""
    cfg_items = _freeze(cfg)
    xs = hidden_states(cfg, seed, tokens)
    w = {k: v.astype(jnp.float32) for k, v in
         W.make_top(W.seed_key(seed), dict(cfg_items), ["norm", "head"]).items()}
    return mm(rms_norm(xs, w["norm"], cfg["rms_norm_eps"]), w["head"])


# --- the comparison that decides a served cell's `correct` ------------------
ROW_PAD = 512  # rows are padded to a multiple, so few shapes ever compile


def served_gap(cfg, seed, samples, pad_to, quant=None):
    """samples: [(prompt ids, served ids)].  The reference runs once over each
    prompt with its served tokens.  Returns (gaps, control_gaps), one number a
    served token: how far the served token's logit lies below the reference's
    best, and, with `quant`, how far the token that the lower precision puts
    first lies below it (the control never decodes)."""
    tokens = np.zeros((len(samples), pad_to), np.int32)
    seq, pos, served = [], [], []
    for i, (prompt, out) in enumerate(samples):
        both = np.concatenate([prompt, out])
        tokens[i, :len(both)] = both
        # served token j was chosen from the logits at position len(prompt)-1+j
        seq += [i] * len(out)
        pos += [len(prompt) - 1 + j for j in range(len(out))]
        served += [int(t) for t in out]
    n = len(served)
    pad = -n % ROW_PAD
    seq, pos, served = (np.asarray(a + [0] * pad, np.int32)
                        for a in (seq, pos, served))
    key, items = W.seed_key(seed), _freeze(cfg)
    xs = hidden_states(cfg, seed, tokens)
    best, at, _ = _pick(xs, seq, pos, served, key, items, None)
    gaps = np.asarray(best - at)[:n]
    control = None
    if quant is not None:
        low = hidden_states(cfg, seed, tokens, quant)
        _, _, first = _pick(low, seq, pos, served, key, items, quant)
        del low
        _, at_low, _ = _pick(xs, seq, pos, first, key, items, None)
        control = np.asarray(best - at_low)[:n]
    return gaps, control
