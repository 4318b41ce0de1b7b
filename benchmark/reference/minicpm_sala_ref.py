"""Plain float32 reference of the MiniCPM-SALA decoder, in jax.numpy.

    h_0 = scale_emb E[id]
    h <- h + r mixer(RMS(h));  h <- h + r W_down(silu(W_gate x) * W_up x), x = RMS(h)
    logits = W_head (RMS(h_L) / (hidden / dim_model_base))

with r = scale_depth / sqrt(PUBLISHED depth).  The mixer of a layer is

  lightning-attn  q = RoPE(RMS_q(W_q x)), k = RoPE(RMS_k(W_k x)) (norm a
      head; rotate-half), v = W_v x; a head h: S_t = lambda_h S_{t-1} + k_t^T
      v_t, o_t = q_t S_t / sqrt(D), lambda_h = exp(-2^(-8h/H)), h = 1..H; y =
      W_o(sigmoid(W_g x) * RMS_o(concat_h o_t)).  A `lax.scan` over tokens.
  minicpm4        q = RMS_q(W_q x), k = RMS_k(W_k x), v = W_v x, no rotary.
      For the query at position t (context n = t + 1) and K/V head g:
      C_j = mean(k_i, i in [16j, 16j + 32)) for every j with 16j + 32 <= n;
      p_h = softmax_j(q_h . C_j / sqrt(D)); s_j = sum_{h in g} p_{h,j};
      block m = tokens [64m, 64m + 64), b_m = max(s_j, j in [4m - 1, 4m + 3]);
      block 0 and every block that overlaps the last 2,048 tokens: +inf; the
      selected set is the 64 best blocks (ties to the lower index), or every
      block where n <= 8192; a_h = softmax over the tokens i <= t of the
      selected blocks of q_h . k_i / sqrt(D), applied to v; y =
      W_o(sigmoid(W_g x) * concat_h a_h).  (Sizes: the file's `sparse_config`.)

It imports nothing of `paddle_tpu` and takes nothing the program made:
weights come from `benchmark.weights_minicpm_sala` by the seed, a layer at a
time (20 GB in float32 do not fit at once).  Matrix products run at
precision "highest".  No kernel, no cache, no batching beyond a map over
sequences; attention runs a block of queries at a time and the feed-forward
a block of tokens at a time, so a 33k-token sequence fits.

Departures from the published model, each in the configuration's `assumed`
too: the decay slopes (the config gives none); no activation on q, k, v; RMS_o
over the concatenated heads; both gates sigmoid; the sparse sizes; `dense_len`
and the selection read PER QUERY POSITION (one function for chunked prefill,
decode and this one pass).

`quant` is for the controls that have to come out not correct: "bf16",
"fp8", "int8" round every matmul operand; "bf16_state" rounds the lightning
state after every token; "all_blocks" attends every block in the minicpm4
layers; "forced_only" selects block 0 and the window alone.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights_minicpm_sala as W
from benchmark.reference.llama_ref import HI, _freeze, _q, rms_norm, rope
from benchmark.reference.llama_ref import mm as _mm

Q_BLOCK = 64       # queries a block of sparse attention
TOKEN_BLOCK = 256  # tokens a block of the feed-forward
VARIANTS = ("bf16_state", "all_blocks", "forced_only")


def quant_mm(quant):
    return None if quant in VARIANTS else quant


def mm(x, w, quant=None):
    return _mm(x, w, quant_mm(quant))


def _blocks(T, want):
    """The largest block <= `want` that divides T."""
    return max(d for d in range(1, min(want, T) + 1) if T % d == 0)


def lightning(u, lw, s, quant=None):
    """u [T, h] -> [T, h]."""
    T = u.shape[0]
    H, D = s["l_heads"], s["l_head_dim"]
    pos = jnp.arange(T)
    q = rope(rms_norm(mm(u, lw["q"], quant).reshape(T, H, D), lw["qn"], s["eps"]),
             pos, s["theta"])
    k = rope(rms_norm(mm(u, lw["k"], quant).reshape(T, H, D), lw["kn"], s["eps"]),
             pos, s["theta"])
    v = mm(u, lw["v"], quant).reshape(T, H, D)
    lam = jnp.exp(-jnp.exp2(-8.0 * jnp.arange(1, H + 1, dtype=jnp.float32) / H))

    def step(S, inp):
        q_t, k_t, v_t = inp
        S = lam[:, None, None] * S + k_t[:, :, None] * v_t[:, None, :]
        if quant == "bf16_state":
            S = S.astype(jnp.bfloat16).astype(jnp.float32)
        return S, jnp.sum(q_t[:, :, None] * S, axis=1)

    _, o = jax.lax.scan(step, jnp.zeros((H, D, D), jnp.float32), (q, k, v))
    o = rms_norm((o / np.sqrt(D)).reshape(T, H * D), lw["on"], s["eps"])
    return mm(jax.nn.sigmoid(mm(u, lw["g"], quant)) * o, lw["o"], quant)


def selected_blocks(q, ck, n, nb, sp, variant=None):
    """Which blocks each query reads.  q [Q, Hq, D] (queries at contexts n
    [Q]), ck [J, Hkv, D] every compressed key of the sequence.  Returns bool
    [Q, Hkv, nb] over the sequence's nb blocks."""
    Q, Hq, D = q.shape
    J, Hkv, _ = ck.shape
    st, ks, blk = sp["kernel_stride"], sp["kernel_size"], sp["block_size"]
    m = jnp.arange(nb)
    in_ctx = m[None, :] * blk < n[:, None]                                # [Q, NB]
    forced = (m[None, :] < sp["init_blocks"]) \
        | ((m[None, :] + 1) * blk > n[:, None] - sp["window_size"])
    if variant == "all_blocks":
        return jnp.broadcast_to(in_ctx[:, None, :], (Q, Hkv, nb))
    if variant == "forced_only":
        return jnp.broadcast_to((in_ctx & forced)[:, None, :], (Q, Hkv, nb))
    logit = jnp.einsum("qgrd,jgd->qgrj", q.reshape(Q, Hkv, Hq // Hkv, D), ck,
                       precision=HI) / np.sqrt(D)
    whole = jnp.arange(J)[None, :] * st + ks <= n[:, None]                # [Q, J]
    logit = jnp.where(whole[:, None, None, :], logit, -jnp.inf)
    top1 = jnp.max(jnp.where(whole[:, None, None, :], logit, -1e30), axis=-1,
                   keepdims=True)
    p = jnp.where(whole[:, None, None, :], jnp.exp(logit - top1), 0.0)
    p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    sj = jnp.sum(p, axis=2)                                               # [Q, Hkv, J]
    # block m is overlapped by the C_j with j*st < (m+1)*blk and j*st + ks > m*blk
    j = jnp.arange(J)
    over = (j[None, :] * st < (m[:, None] + 1) * blk) \
        & (j[None, :] * st + ks > m[:, None] * blk)                       # [NB, J]
    b = jnp.max(jnp.where(over[None, None], sj[:, :, None, :], 0.0), axis=-1)
    score = jnp.where(in_ctx[:, None, :],
                      jnp.where(forced[:, None, :], jnp.inf, b), -jnp.inf)
    _, idx = jax.lax.top_k(score, min(sp["topk"], nb))
    top = jnp.zeros((Q, Hkv, nb), bool).at[
        jnp.arange(Q)[:, None, None], jnp.arange(Hkv)[None, :, None], idx].set(True)
    dense = (n <= sp["dense_len"])[:, None, None]
    return in_ctx[:, None, :] & (dense | top)


def sparse_attention(u, lw, s, quant=None):
    """u [T, h] -> [T, h]."""
    T = u.shape[0]
    Hq, Hkv, D = s["heads"], s["kv_heads"], s["head_dim"]
    sp = s["sparse"]
    st, ks, blk = sp["kernel_stride"], sp["kernel_size"], sp["block_size"]
    qm = quant_mm(quant)
    variant = quant if quant in VARIANTS else None
    q = rms_norm(mm(u, lw["q"], quant).reshape(T, Hq, D), lw["qn"], s["eps"])
    k = rms_norm(mm(u, lw["k"], quant).reshape(T, Hkv, D), lw["kn"], s["eps"])
    v = mm(u, lw["v"], quant).reshape(T, Hkv, D)
    J = max((T - ks) // st + 1, 1)
    kpad = jnp.pad(k, ((0, max(0, (J - 1) * st + ks - T)), (0, 0), (0, 0)))
    # C_j = mean(k[j st : j st + ks]): the ks shifted views, every st-th row
    ck = jnp.mean(jnp.stack([kpad[d:d + (J - 1) * st + 1:st] for d in range(ks)]),
                  axis=0)
    nb = -(-T // blk)
    qb = _blocks(T, Q_BLOCK)
    kpos = jnp.arange(T)
    rep = Hq // Hkv

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, 0)
        qpos = i * qb + jnp.arange(qb)
        sel = selected_blocks(qi, ck, qpos + 1, nb, sp, variant)          # [qb,Hkv,nb]
        tok = jnp.repeat(sel, blk, axis=-1)[..., :T]
        ok = tok & (kpos[None, None, :] <= qpos[:, None, None])           # [qb,Hkv,T]
        sc = jnp.einsum("qgrd,kgd->qgrk", _q(qi, qm).reshape(qb, Hkv, rep, D),
                        _q(k, qm), precision=HI) / np.sqrt(D)
        sc = jnp.where(ok[:, :, None, :], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("qgrk,kgd->qgrd", _q(p, qm), _q(v, qm), precision=HI)

    a = jax.lax.map(block, jnp.arange(T // qb)).reshape(T, Hq * D)
    return mm(jax.nn.sigmoid(mm(u, lw["g"], quant)) * a, lw["o"], quant)


def feed_forward(x, lw, quant=None):
    T, h = x.shape
    tb = _blocks(T, TOKEN_BLOCK)

    def block(xb):
        return mm(jax.nn.silu(mm(xb, lw["gate"], quant)) * mm(xb, lw["up"], quant),
                  lw["down"], quant)

    return jax.lax.map(block, x.reshape(T // tb, tb, h)).reshape(T, h)


def layer(x, lw, kind, s, quant=None):
    """One layer over one sequence x [T, h]; lw float32 leaves by short name."""
    mixer = sparse_attention if kind == W.SPARSE else lightning
    x = x + s["residual"] * mixer(rms_norm(x, lw["ln1"], s["eps"]), lw, s, quant)
    return x + s["residual"] * feed_forward(rms_norm(x, lw["ln2"], s["eps"]), lw, quant)


def _freeze_cfg(cfg):
    sub = {k: cfg[k] for k in ("sparse_config", "published") if k in cfg}
    flat = {k: v for k, v in cfg.items() if k not in sub and k != "mixer_types"}
    return _freeze(flat) + (("mixer_types", tuple(cfg["mixer_types"])),) + tuple(
        (k, tuple(sorted((a, tuple(b) if isinstance(b, list) else b)
                         for a, b in v.items()))) for k, v in sorted(sub.items()))


def _thaw(items):
    cfg = {}
    for k, v in items:
        cfg[k] = dict(v) if k in ("sparse_config", "published") else v
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
    cfg = _thaw(cfg_items)
    e = W.make_top(key_data, cfg, ["embed"])["embed"]
    return e.astype(jnp.float32)[tokens] * cfg["scale_emb"]


def _logits(x, w, s, quant=None):
    return mm(rms_norm(x, w["norm"], s["eps"]) / s["head_div"], w["head"], quant)


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
    a sequence's end is harmless: every mixer is causal), layer by layer."""
    key, items = W.seed_key(seed), _freeze_cfg(cfg)
    xs = _embed(jnp.asarray(tokens, jnp.int32), key, items)
    for i, kind in enumerate(cfg["mixer_types"]):
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
