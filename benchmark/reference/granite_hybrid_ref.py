"""Plain float32 reference of the Granite-4.0-H hybrid decoder, in jax.numpy.

  x = embedding_multiplier * E[ids]
  every layer:  u = RMSNorm(x);  x <- x + residual_multiplier * mixer(u)
                u = RMSNorm(x);  [g, up] = W_in u (the first half the gate)
                x <- x + residual_multiplier * W_out (silu(g) * up)
  logits = RMSNorm(x) E^T / logits_scaling      (the head is the embedding)

The mixer is the layer's `layer_types` entry:

  mamba      Mamba-2 with one group: benchmark/reference/nemotron_h_ref.py's
             `mamba`, imported unchanged (the recurrence a `lax.scan` over
             tokens; the gated RMSNorm over inner / groups channels = all of
             them at one group, as the family's MambaRMSNormGated)
  attention  causal grouped-query attention, head_dim = hidden / heads, no
             positional encoding, softmax scale attention_multiplier, no bias

It imports nothing of `paddle_tpu` and takes nothing the program made:
weights come from `benchmark.weights_granite_hybrid` by the seed, a layer at
a time.  Everything runs under `jax.default_matmul_precision("highest")`.

Departures from the HF modelling code (`modeling_granitemoehybrid.py`):
- the gated RMSNorm multiplies its weight in float32 before the cast (HF
  casts to the input dtype first): no difference in float32;
- `time_step_limit` (0, inf) clamps nothing and is not applied;
- no attention or residual dropout (inference), no MoE branch (num_local_
  experts is 0), no rotary table (position_embedding_type "nope").

`quant` is for the controls that have to come out not correct: "bf16",
"fp8" or "int8" round every matmul operand of the mixers, the MLPs and the
head; "bf16_state" rounds the SSM state after every token and nothing else.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights_granite_hybrid as W
from benchmark.reference.llama_ref import HI, _freeze, _q, rms_norm
from benchmark.reference.nemotron_h_ref import ROW_PAD, mamba, mm, quant_mm

Q_BLOCK = 128


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
                        precision=HI) * s["attn_mult"]
        qpos = i * qb + jnp.arange(qb)
        sc = jnp.where(kpos[None, None, :] <= qpos[None, :, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("hqk,khd->qhd", _q(p, qm), _q(v, qm), precision=HI)

    a = jax.lax.map(block, jnp.arange(T // qb)).reshape(T, Hq * D)
    return mm(a, lw["o"], quant)


def mlp(u, lw, s, quant=None):
    gu = mm(u, lw["mlp_in"], quant)
    F = s["ffn"]
    return mm(jax.nn.silu(gu[:, :F]) * gu[:, F:], lw["mlp_out"], quant)


def layer(x, lw, kind, s, quant=None):
    """One layer over one sequence x [T, h]; lw float32 leaves by short name."""
    u = rms_norm(x, lw["ln"], s["eps"])
    mix = mamba(u, lw, s, quant) if kind == "mamba" else attention(u, lw, s, quant)
    x = x + s["res_mult"] * mix
    return x + s["res_mult"] * mlp(rms_norm(x, lw["post_ln"], s["eps"]), lw, s, quant)


def _freeze_cfg(cfg):
    """The config's numbers and its layer_types, hashable (a static argument)."""
    return _freeze(cfg) + (("layer_types", tuple(cfg["layer_types"])),)


def _thaw(items):
    return dict(items)


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
    return e.astype(jnp.float32)[tokens] * cfg["embedding_multiplier"]


def _logits(x, w, cfg, quant=None):
    """The tied head: RMSNorm(x) E^T / logits_scaling."""
    return mm(rms_norm(x, w["norm"], cfg["rms_norm_eps"]), w["embed"].T,
              quant) / cfg["logits_scaling"]


def _top32(cfg, key_data):
    return {k: v.astype(jnp.float32)
            for k, v in W.make_top(key_data, cfg).items()}


@functools.partial(jax.jit, static_argnames=("cfg_items", "quant"))
def _pick(xs, seq, pos, pick, key_data, cfg_items, quant):
    """Logits at rows (seq, pos) of xs: (best, logit of `pick`, argmax)."""
    cfg = _thaw(cfg_items)
    lg = _logits(xs[seq, pos], _top32(cfg, key_data), cfg, quant)
    at = jnp.take_along_axis(lg, pick[:, None], axis=-1)[:, 0]
    return jnp.max(lg, axis=-1), at, jnp.argmax(lg, axis=-1).astype(jnp.int32)


def hidden_states(cfg, seed, tokens, quant=None):
    """Final pre-norm hidden states [n, T, h] of `tokens` [n, T] (padding past
    a sequence's end is harmless: every mixer is causal), layer by layer."""
    key, items = W.seed_key(seed), _freeze_cfg(cfg)
    with jax.default_matmul_precision("highest"):
        xs = _embed(jnp.asarray(tokens, jnp.int32), key, items)
        for i, kind in enumerate(cfg["layer_types"]):
            xs = _layer_step(xs, key, jnp.int32(i), items, kind, quant)
    return xs


def full_logits(cfg, seed, tokens):
    """[n, T, V] float32: for the small sizes of the tests."""
    xs = hidden_states(cfg, seed, tokens)
    with jax.default_matmul_precision("highest"):
        return _logits(xs, _top32(cfg, W.seed_key(seed)), cfg)


# --- the comparison that decides a served cell's `correct` ------------------
def served_gap(cfg, seed, samples, pad_to, controls=()):
    """samples: [(prompt ids, served ids)].  The reference runs once over each
    prompt with its served tokens (teacher forced).  Returns (gaps,
    {control: gaps}), one number a served token: how far the served token's
    logit lies below the reference's best, and, for each `quant` named in
    `controls`, how far the token that the lower precision puts first lies
    below it."""
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
    with jax.default_matmul_precision("highest"):
        best, at, _ = _pick(xs, seq, pos, served, key, items, None)
    gaps = np.asarray(best - at)[:n]
    control = {}
    for quant in controls:
        low = hidden_states(cfg, seed, tokens, quant)
        with jax.default_matmul_precision("highest"):
            _, _, first = _pick(low, seq, pos, served, key, items, quant)
            del low
            _, at_low, _ = _pick(xs, seq, pos, first, key, items, None)
        control[quant] = np.asarray(best - at_low)[:n]
    return gaps, control
