"""Plain float32 reference of the Nemotron-H hybrid decoder, in jax.numpy.

Every layer is x <- x + mixer(RMSNorm(x)); the mixer is one of

  M  Mamba-2.  [z, xBC, dt] = W_in u; xBC <- silu(causal depthwise conv(xBC)
     + b); xBC -> x [H, P], B, C [G, N] (head h uses group h // (H / G));
     dt <- softplus(dt + dt_bias); A = -exp(A_log);
     S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t;  y_t = S_t C_t + D x_t;
     y <- RMSNorm over groups of inner / G of (y silu(z)), times its weight;
     out = W_out y.  The recurrence is a `lax.scan` over tokens.
  E  s = sigmoid(W_r u) over all published experts; the top k by
     s + router_bias; weights s / (sum s + 1e-20) x routed_scaling_factor;
     expert e adds w W2_e relu(W1_e u)^2; a shared expert is added for every
     token.  A loop over the experts, every token through each, masked.
  *  causal grouped-query attention with the config's head_dim, scale
     head_dim^-1/2, no bias.

It imports nothing of `paddle_tpu` and takes nothing the program made:
weights come from `benchmark.weights_nemotron_h` by the seed, a layer at a
time.  Matrix products run at precision "highest".  No kernel, no cache, no
batching beyond a map over sequences.

Departures from the published model, each the configuration's own:
- NO rotary embedding in the attention layers: the family's modelling code
  applies none there (the Mamba layers carry order); `rope_theta` is unread.
- Given the same share as the program: only the held experts
  (`share.experts_held`) add their part, what an absent expert would add is
  left out, and the logits are over the vocabulary slice.

`quant` is for the control that has to come out not correct: "bf16", "fp8"
or "int8" round every matmul operand of the mixers and the head (the router
stays float32, as the configuration states it); "bf16_state" rounds the SSM
state after every token and nothing else.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights_nemotron_h as W
from benchmark.reference.llama_ref import HI, _freeze, _q, rms_norm
from benchmark.reference.llama_ref import mm as _mm

Q_BLOCK = 128


def quant_mm(quant):
    return None if quant == "bf16_state" else quant


def mm(x, w, quant=None):
    return _mm(x, w, quant_mm(quant))


def softplus(x):
    return jnp.where(x > 20.0, x, jnp.log1p(jnp.exp(jnp.minimum(x, 20.0))))


def mamba(u, lw, s, quant=None):
    """u [T, h] -> [T, h]."""
    T = u.shape[0]
    H, P, G, N, K = s["m_heads"], s["m_head_dim"], s["groups"], s["state"], s["conv_k"]
    inner, C = s["inner"], s["conv_c"]
    proj = mm(u, lw["in_proj"], quant)
    z, xbc, dt = proj[:, :inner], proj[:, inner:inner + C], proj[:, inner + C:]
    ext = jnp.concatenate([jnp.zeros((K - 1, C), jnp.float32), xbc])
    conv = lw["conv_b"] + sum(ext[k:k + T] * lw["conv_w"][k] for k in range(K))
    act = jax.nn.silu(conv)
    x = act[:, :inner].reshape(T, H, P)
    B = jnp.repeat(act[:, inner:inner + G * N].reshape(T, G, N), H // G, axis=1)
    Cm = jnp.repeat(act[:, inner + G * N:].reshape(T, G, N), H // G, axis=1)
    dt = softplus(dt + lw["dt_bias"])
    A = -jnp.exp(lw["A_log"])

    def step(S, inp):
        x_t, b_t, c_t, dt_t = inp
        S = jnp.exp(dt_t * A)[:, None, None] * S \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        if quant == "bf16_state":
            S = S.astype(jnp.bfloat16).astype(jnp.float32)
        return S, jnp.sum(S * c_t[:, None, :], axis=-1)

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), jnp.float32), (x, B, Cm, dt))
    y = (y + lw["D"][:, None] * x).reshape(T, inner) * jax.nn.silu(z)
    yg = y.reshape(T, G, inner // G)
    yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, axis=-1, keepdims=True) + s["eps"])
    return mm(yg.reshape(T, inner) * lw["mnorm"], lw["out_proj"], quant)


def route(u, lw, s):
    """(expert [T, k] among ALL published experts, weight [T, k])."""
    sc = jax.nn.sigmoid(jnp.matmul(u, lw["router"], precision=HI))
    _, idx = jax.lax.top_k(sc + lw["router_bias"], s["top_k"])
    w = jnp.take_along_axis(sc, idx, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx, w * s["scaling"]


def experts(u, lw, s, held, quant=None):
    """The part the experts `held` = (lo, hi) of the published ones give.
    lw["up"], lw["down"] hold exactly those, [hi - lo, F, h]."""
    idx, w = route(u, lw, s)
    lo, hi = held

    def one(acc, e):
        cw = jnp.sum(jnp.where(idx == e + lo, w, 0.0), axis=-1)   # [T]
        up = jax.lax.dynamic_index_in_dim(lw["up"], e, 0, keepdims=False)
        down = jax.lax.dynamic_index_in_dim(lw["down"], e, 0, keepdims=False)
        hid = jnp.square(jnp.maximum(mm(u, up.T, quant), 0.0))
        return acc + cw[:, None] * mm(hid, down, quant), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(u), jnp.arange(hi - lo))
    return out


def shared_expert(u, lw, quant=None):
    hid = jnp.square(jnp.maximum(mm(u, lw["shared_up"], quant), 0.0))
    return mm(hid, lw["shared_down"], quant)


def attention(u, lw, s, quant=None):
    T = u.shape[0]
    Hq, Hkv, D = s["heads"], s["kv_heads"], s["head_dim"]
    q = mm(u, lw["q"], quant).reshape(T, Hq, D)
    k = jnp.repeat(mm(u, lw["k"], quant).reshape(T, Hkv, D), Hq // Hkv, axis=1)
    v = jnp.repeat(mm(u, lw["v"], quant).reshape(T, Hkv, D), Hq // Hkv, axis=1)
    qb = min(Q_BLOCK, T)
    if T % qb:
        raise ValueError(f"sequence {T} is not a multiple of {qb}")
    kpos = jnp.arange(T)
    qm = quant_mm(quant)

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, 0)
        sc = jnp.einsum("qhd,khd->hqk", _q(qi, qm), _q(k, qm),
                        precision=HI) / np.sqrt(D)
        qpos = i * qb + jnp.arange(qb)
        sc = jnp.where(kpos[None, None, :] <= qpos[None, :, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("hqk,khd->qhd", _q(p, qm), _q(v, qm), precision=HI)

    a = jax.lax.map(block, jnp.arange(T // qb)).reshape(T, Hq * D)
    return mm(a, lw["o"], quant)


def layer(x, lw, kind, s, quant=None, held=None):
    """One layer over one sequence x [T, h]; lw float32 leaves by short name."""
    u = rms_norm(x, lw["ln"], s["eps"])
    if kind == "M":
        return x + mamba(u, lw, s, quant)
    if kind == "*":
        return x + attention(u, lw, s, quant)
    return x + experts(u, lw, s, held or s["held"], quant) + shared_expert(u, lw, quant)


@functools.partial(jax.jit, static_argnames=("cfg_items", "kind", "quant"))
def _layer_step(xs, key_data, i, cfg_items, kind, quant):
    """Draw layer i's weights and apply it to every sequence of xs [n, T, h]."""
    cfg = _thaw(cfg_items)
    s = W.sizes(cfg)
    lw = {k: v.astype(jnp.float32)
          for k, v in W.make_layer(key_data, cfg, i, kind).items()}
    return jax.lax.map(lambda x: layer(x, lw, kind, s, quant), xs)


def _freeze_cfg(cfg):
    share = cfg.get("share", {})
    return _freeze(cfg) + tuple(
        ("share." + k, tuple(v) if isinstance(v, list) else v)
        for k, v in sorted(share.items()))


def _thaw(items):
    cfg, share = {}, {}
    for k, v in items:
        if k.startswith("share."):
            share[k[6:]] = v
        else:
            cfg[k] = v
    if share:
        cfg["share"] = share
    return cfg


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _embed(tokens, key_data, cfg_items):
    e = W.make_top(key_data, _thaw(cfg_items), ["embed"])["embed"]
    return e.astype(jnp.float32)[tokens]


@functools.partial(jax.jit, static_argnames=("cfg_items", "quant"))
def _pick(xs, seq, pos, pick, key_data, cfg_items, quant):
    """Logits at rows (seq, pos) of xs: (best, logit of `pick`, argmax)."""
    cfg = _thaw(cfg_items)
    w = {k: v.astype(jnp.float32)
         for k, v in W.make_top(key_data, cfg, ["norm", "head"]).items()}
    lg = mm(rms_norm(xs[seq, pos], w["norm"], cfg["layer_norm_epsilon"]),
            w["head"], quant)
    at = jnp.take_along_axis(lg, pick[:, None], axis=-1)[:, 0]
    return jnp.max(lg, axis=-1), at, jnp.argmax(lg, axis=-1).astype(jnp.int32)


def hidden_states(cfg, seed, tokens, quant=None):
    """Final pre-norm hidden states [n, T, h] of `tokens` [n, T] (padding past
    a sequence's end is harmless: every mixer is causal), layer by layer."""
    key, items = W.seed_key(seed), _freeze_cfg(cfg)
    xs = _embed(jnp.asarray(tokens, jnp.int32), key, items)
    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        xs = _layer_step(xs, key, jnp.int32(i), items, kind, quant)
    return xs


def full_logits(cfg, seed, tokens):
    """[n, T, V] float32: for the small sizes of the tests."""
    xs = hidden_states(cfg, seed, tokens)
    w = {k: v.astype(jnp.float32) for k, v in
         W.make_top(W.seed_key(seed), cfg, ["norm", "head"]).items()}
    return mm(rms_norm(xs, w["norm"], cfg["layer_norm_epsilon"]), w["head"])


# --- the comparison that decides a served cell's `correct` ------------------
ROW_PAD = 512  # rows are padded to a multiple, so few shapes ever compile


def served_gap(cfg, seed, samples, pad_to, quant=None):
    """samples: [(prompt ids, served ids)].  The reference runs once over each
    prompt with its served tokens (teacher forced).  Returns (gaps,
    control_gaps), one number a served token: how far the served token's
    logit lies below the reference's best, and, with `quant`, how far the
    token that the lower precision puts first lies below it."""
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
    key, items = W.seed_key(seed), _freeze_cfg(cfg)
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
