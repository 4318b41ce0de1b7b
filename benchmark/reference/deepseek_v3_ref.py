"""Plain float32 reference of a DeepSeek-V3-shaped decoder (Kanana-2-30B-A3B),
in jax.numpy: multi-head latent attention in its EXPANDED form and gated
routed experts with shared ones.

    x <- x + Attn(RMS(x));  x <- x + FFN(RMS(x));  logits = W_head RMS(x_L)

  Attn  q = W_q u, a head's q = [q^nope (128) ; q^rope (64)];
        [c ; k^rope] = W_kva u; c <- RMS(c) over the 512; q^rope, k^rope <-
        RoPE at the token's position, ADJACENT pairs (2i, 2i+1) by t
        theta^(-2i/64) (`rope_interleave`); [k^nope_h ; v_h] = W_kvb,h c for
        EVERY head and key (materialised: nothing here is absorbed, so this
        is independent of the program's absorbed decode);
        s = (q^nope_h . k^nope_h + q^rope_h . k^rope) / sqrt(192), causal
        soft-max, W_o [o_1 ; ...]
  FFN   layer < first_k_dense_replace: W_down(silu(W_gate u) * W_up u).
        After: s = sigmoid(W_r u) over all published experts in float32; the
        top k by s + router_bias; weights s / (sum s + 1e-20) x
        routed_scaling_factor; expert e adds w W_down,e(silu(W_gate,e u) *
        W_up,e u); the shared experts, one gated MLP of their summed width,
        are added for every token.  A loop over the experts, every token
        through each, masked.

It imports nothing of `paddle_tpu` and takes nothing the program made:
weights come from `benchmark.weights_deepseek_v3` by the seed, a layer at a
time (20 GB in float32 do not fit at once).  Matrix products run at precision
"highest".  No kernel, no cache, no batching beyond a map over sequences;
attention runs a block of queries at a time, so a 17k-token sequence at the
published widths fits.

Departures from the published model, each in the configuration's `assumed`
too:
- the family's code de-interleaves q^rope and k^rope by one permutation and
  rotates halves; here adjacent pairs are rotated in place.  The scores are
  the same (a permutation q and k share), the weights keep the published row
  order.
- `n_group` = `topk_group` = 1: no group-limited routing is computed.
- Given the same share as the program: only the held experts
  (`share.experts_held`, all 128 in the benchmark's configuration) add their
  part; what an absent expert would add is left out.

`quant` is for the controls that have to come out not correct: "bf16",
"fp8", "int8" round every matmul operand of attention, feed-forward and head
(the router stays float32, as the configuration states it); "fp8_latent"
rounds the cached latent and rotary key to fp8 and nothing else;
"no_rope_score" leaves the rotary part out of the score; "unrotated_k" leaves
k^rope un-rotated.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights_deepseek_v3 as W
from benchmark.reference.llama_ref import HI, _freeze, _q, rms_norm
from benchmark.reference.llama_ref import mm as _mm

Q_BLOCK = 64
VARIANTS = ("fp8_latent", "no_rope_score", "unrotated_k")


def quant_mm(quant):
    return None if quant in VARIANTS else quant


def mm(x, w, quant=None):
    return _mm(x, w, quant_mm(quant))


def rope_pairs(x, pos, theta):
    """x [T, ..., D], pos [T]: adjacent pairs (2i, 2i+1) rotated by
    pos theta^(-2i/D)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,))
    c, s = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * c - b * s, b * c + a * s], axis=-1).reshape(x.shape)


def _blocks(T, want):
    """The largest block <= `want` that divides T."""
    return max(d for d in range(1, min(want, T) + 1) if T % d == 0)


def attention(u, lw, s, quant=None):
    """u [T, h] -> [T, h]: every head's keys and values materialised."""
    T = u.shape[0]
    H, dn, dr, dv, dc = s["heads"], s["nope"], s["rope"], s["v"], s["latent"]
    qm = quant_mm(quant)
    pos = jnp.arange(T)
    q = mm(u, lw["q"], quant).reshape(T, H, dn + dr)
    q_nope, q_rope = q[..., :dn], rope_pairs(q[..., dn:], pos, s["theta"])
    kva = mm(u, lw["kva"], quant)
    c = rms_norm(kva[:, :dc], lw["kvn"], s["eps"])
    k_rope = kva[:, dc:]
    if quant != "unrotated_k":
        k_rope = rope_pairs(k_rope, pos, s["theta"])
    if quant == "fp8_latent":
        c, k_rope = _q(c, "fp8"), _q(k_rope, "fp8")
    kv = mm(c, lw["kvb"], quant).reshape(T, H, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    qb = _blocks(T, Q_BLOCK)

    def block(i):
        qn = jax.lax.dynamic_slice_in_dim(q_nope, i * qb, qb, 0)
        qr = jax.lax.dynamic_slice_in_dim(q_rope, i * qb, qb, 0)
        sc = jnp.einsum("qhd,khd->hqk", _q(qn, qm), _q(k_nope, qm), precision=HI)
        if quant != "no_rope_score":
            sc = sc + jnp.einsum("qhd,kd->hqk", _q(qr, qm), _q(k_rope, qm),
                                 precision=HI)
        sc = sc / np.sqrt(dn + dr)
        qpos = i * qb + jnp.arange(qb)
        sc = jnp.where(pos[None, None, :] <= qpos[None, :, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("hqk,khd->qhd", _q(p, qm), _q(v, qm), precision=HI)

    a = jax.lax.map(block, jnp.arange(T // qb)).reshape(T, H * dv)
    return mm(a, lw["o"], quant)


def gated(u, gate, up, down, quant=None):
    return mm(jax.nn.silu(mm(u, gate, quant)) * mm(u, up, quant), down, quant)


def route(u, lw, s):
    """Float32 whatever the control: expert [T, K] and weight [T, K]."""
    sc = jax.nn.sigmoid(jnp.matmul(u, lw["router"], precision=HI))
    _, idx = jax.lax.top_k(sc + lw["router_bias"], s["top_k"])
    w = jnp.take_along_axis(sc, idx, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx, w * s["scaling"]


def experts(u, lw, s, held, quant=None):
    """The part the experts `held` = (lo, hi) of the published ones give.
    lw["egate"], lw["eup"], lw["edown"] hold exactly those, [hi - lo, F, h]."""
    idx, w = route(u, lw, s)
    lo, hi = held

    def one(acc, e):
        cw = jnp.sum(jnp.where(idx == e + lo, w, 0.0), axis=-1)   # [T]
        g, up, down = (jax.lax.dynamic_index_in_dim(lw[k], e, 0, keepdims=False)
                       for k in ("egate", "eup", "edown"))
        return acc + cw[:, None] * gated(u, g.T, up.T, down, quant), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(u), jnp.arange(hi - lo))
    return out


def shared_experts(u, lw, quant=None):
    return gated(u, lw["sgate"], lw["sup"], lw["sdown"], quant)


def layer(x, lw, kind, s, quant=None):
    """One layer over one sequence x [T, h]; lw float32 leaves by short name."""
    x = x + attention(rms_norm(x, lw["ln1"], s["eps"]), lw, s, quant)
    u = rms_norm(x, lw["ln2"], s["eps"])
    if kind == "D":
        return x + gated(u, lw["gate"], lw["up"], lw["down"], quant)
    return x + experts(u, lw, s, s["held"], quant) + shared_experts(u, lw, quant)


def _freeze_cfg(cfg):
    """Hashable: the scalar keys, the two published as null, the share."""
    share = cfg.get("share", {})
    return _freeze(cfg) + (
        ("q_lora_rank", cfg["q_lora_rank"]), ("rope_scaling", cfg["rope_scaling"]),
        ("share", tuple(sorted((k, tuple(v)) for k, v in share.items()))))


def _thaw(items):
    cfg = dict(items)
    cfg["share"] = {k: list(v) for k, v in cfg["share"]}
    return cfg


@functools.partial(jax.jit, static_argnames=("cfg_items", "kind", "quant"))
def _layer_step(xs, key_data, i, cfg_items, kind, quant):
    """Draw layer i's weights and apply it to every sequence of xs [n, T, h]."""
    cfg = _thaw(cfg_items)
    s = W.sizes(cfg)
    lw = {k: v.astype(jnp.float32)
          for k, v in W.make_layer(key_data, cfg, i, kind).items()}
    return jax.lax.map(lambda x: layer(x, lw, kind, s, quant), xs)


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _embed(tokens, key_data, cfg_items):
    e = W.make_top(key_data, _thaw(cfg_items), ["embed"])["embed"]
    return e.astype(jnp.float32)[tokens]


def _logits(x, w, s, quant=None):
    return mm(rms_norm(x, w["norm"], s["eps"]), w["head"], quant)


@functools.partial(jax.jit, static_argnames=("cfg_items", "quant"))
def _pick(xs, seq, pos, pick, key_data, cfg_items, quant):
    """Logits at rows (seq, pos) of xs: (best, logit of `pick`, argmax)."""
    cfg = _thaw(cfg_items)
    w = {k: v.astype(jnp.float32)
         for k, v in W.make_top(key_data, cfg, ["norm", "head"]).items()}
    lg = _logits(xs[seq, pos], w, W.sizes(cfg), quant)
    at = jnp.take_along_axis(lg, pick[:, None], axis=-1)[:, 0]
    return jnp.max(lg, axis=-1), at, jnp.argmax(lg, axis=-1).astype(jnp.int32)


def hidden_states(cfg, seed, tokens, quant=None):
    """Final pre-norm hidden states [n, T, h] of `tokens` [n, T] (padding past
    a sequence's end is harmless: attention is causal), layer by layer."""
    key, items = W.seed_key(seed), _freeze_cfg(cfg)
    xs = _embed(jnp.asarray(tokens, jnp.int32), key, items)
    for i, kind in enumerate(W.sizes(cfg)["kinds"]):
        xs = _layer_step(xs, key, jnp.int32(i), items, kind, quant)
    return xs


def full_logits(cfg, seed, tokens):
    """[n, T, V] float32: for the small sizes of the tests."""
    xs = hidden_states(cfg, seed, tokens)
    w = {k: v.astype(jnp.float32) for k, v in
         W.make_top(W.seed_key(seed), cfg, ["norm", "head"]).items()}
    return _logits(xs, w, W.sizes(cfg))


# --- the comparison that decides a served cell's `correct` ------------------
ROW_PAD = 512  # rows are padded to a multiple, so few shapes ever compile


def served_gap(cfg, seed, samples, pad_to, quants=()):
    """samples: [(prompt ids, served ids)].  The reference runs once over each
    prompt with its served tokens (teacher forced).  Returns (gaps,
    {quant: control gaps}), one number a served token: how far the served
    token's logit lies below the reference's best and, for each control, how
    far the token that the control puts first lies below it."""
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
    controls = {}
    for quant in quants:
        low = hidden_states(cfg, seed, tokens, quant)
        _, _, first = _pick(low, seq, pos, served, key, items, quant_mm(quant))
        del low
        _, at_low, _ = _pick(xs, seq, pos, first, key, items, None)
        controls[quant] = np.asarray(best - at_low)[:n]
    return gaps, controls
