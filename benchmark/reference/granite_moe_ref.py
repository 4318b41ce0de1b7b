"""Plain float32 reference of the Granite-4.0-H decoder with routed experts
(Small), in jax.numpy.

  x = embedding_multiplier * E[ids]
  every layer:  u = RMSNorm(x);  x <- x + residual_multiplier * mixer(u)
                v = RMSNorm(x)
                l = W_r v over ALL the router's experts; i_1..i_K the K largest
                g = softmax(l_{i_1..i_K})          (over the chosen K alone)
                moe(v) = sum_k g_k W2[i_k] (silu(Wg[i_k] v) * W1[i_k] v)
                x <- x + residual_multiplier * (moe(v) + shared(v))
  logits = RMSNorm(x) E^T / logits_scaling      (the head is the embedding)

The mixers, the shared MLP, the embedding and the tied head are
benchmark/reference/granite_hybrid_ref.py's (`attention`, `mlp`, `_embed`,
`_pick`; Mamba-2 is nemotron_h_ref's `mamba` through it), imported unchanged:
benchmark.weights_granite_moe draws every leaf Micro has under Micro's keys.
The experts are a loop over the held ones, every token through each, masked.
It imports nothing of `paddle_tpu` and takes nothing the program made:
weights come from `benchmark.weights_granite_moe` by the seed, a layer at a
time.  Everything runs under `jax.default_matmul_precision("highest")`.

Departures from the HF modelling code (`modeling_granitemoehybrid.py`), each
the configuration's own:
- given the same share as the program: only the held experts
  (`share.experts_held`) add their part, what an absent expert would add is
  left out, and the logits are over the vocabulary slice;
- the router's logits are float32 (HF computes them in the weights' dtype
  and casts them up) and the gates stay float32 (HF casts them back);
- as granite_hybrid_ref: no dropout, no rotary table, no time_step_limit.

`control` is for the controls that have to come out not correct: "fp8" or
"int8" round every matmul operand of the mixers, the MLPs, the experts and
the head (the router stays float32); "softmax_all" weighs the chosen experts
by a softmax over ALL the router's logits; "held_renorm" renormalises the
gates over the chosen experts that are held here.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights_granite_moe as W
from benchmark.reference import granite_hybrid_ref as _dense
from benchmark.reference.granite_hybrid_ref import attention, mlp
from benchmark.reference.llama_ref import HI, rms_norm
from benchmark.reference.nemotron_h_ref import ROW_PAD, _thaw, mamba, mm
from benchmark.reference.nemotron_h_ref import _freeze_cfg as _freeze_share

ROUTER_FORMS = ("softmax_all", "held_renorm")


def route(v, lw, s, form=None):
    """(expert [T, K] among ALL the router's experts, gate [T, K])."""
    logits = jnp.matmul(v, lw["router"], precision=HI)
    top, idx = jax.lax.top_k(logits, s["top_k"])
    if form == "softmax_all":
        return idx, jnp.take_along_axis(jax.nn.softmax(logits, axis=-1), idx, -1)
    g = jax.nn.softmax(top, axis=-1)
    if form == "held_renorm":
        lo, hi = s["held"]
        g = jnp.where((idx >= lo) & (idx < hi), g, 0.0)
        g = g / jnp.maximum(jnp.sum(g, axis=-1, keepdims=True), 1e-20)
    return idx, g


def experts(v, lw, s, quant=None, form=None):
    """The part the held experts s["held"] = (lo, hi) give; lw["e_*"] hold
    exactly those, [hi - lo, F, h]."""
    idx, g = route(v, lw, s, form)
    lo, hi = s["held"]

    def one(acc, e):
        cw = jnp.sum(jnp.where(idx == e + lo, g, 0.0), axis=-1)   # [T]
        mat = {k: jax.lax.dynamic_index_in_dim(lw[k], e, 0, keepdims=False)
               for k in W.EXPERTS}
        hid = jax.nn.silu(mm(v, mat["e_gate"].T, quant)) * mm(v, mat["e_up"].T, quant)
        return acc + cw[:, None] * mm(hid, mat["e_down"], quant), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(v), jnp.arange(hi - lo))
    return out


def layer(x, lw, kind, s, quant=None, form=None):
    """One layer over one sequence x [T, h]; lw float32 leaves by short name."""
    u = rms_norm(x, lw["ln"], s["eps"])
    mix = mamba(u, lw, s, quant) if kind == "mamba" else attention(u, lw, s, quant)
    x = x + s["res_mult"] * mix
    v = rms_norm(x, lw["post_ln"], s["eps"])
    return x + s["res_mult"] * (experts(v, lw, s, quant, form) + mlp(v, lw, s, quant))


def _freeze_cfg(cfg):
    """The config's numbers, its share and its layer_types, hashable."""
    return _freeze_share(cfg) + (("layer_types", tuple(cfg["layer_types"])),)


def _split(control):
    """A control's name -> (quant, router form)."""
    return (None, control) if control in ROUTER_FORMS else (control, None)


@functools.partial(jax.jit, static_argnames=("cfg_items", "kind", "control"))
def _layer_step(xs, key_data, i, cfg_items, kind, control):
    """Draw layer i's weights and apply it to every sequence of xs [n, T, h]."""
    cfg = _thaw(cfg_items)
    s = W.sizes(cfg)
    lw = {k: v.astype(jnp.float32)
          for k, v in W.make_layer(key_data, cfg, i, kind).items()}
    return jax.lax.map(lambda x: layer(x, lw, kind, s, *_split(control)), xs)


def hidden_states(cfg, seed, tokens, control=None):
    """Final pre-norm hidden states [n, T, h] of `tokens` [n, T] (padding past
    a sequence's end is harmless: every mixer is causal), layer by layer."""
    key, items = W.seed_key(seed), _freeze_cfg(cfg)
    with jax.default_matmul_precision("highest"):
        xs = _dense._embed(jnp.asarray(tokens, jnp.int32), key,
                           _dense._freeze_cfg(cfg))
        for i, kind in enumerate(cfg["layer_types"]):
            xs = _layer_step(xs, key, jnp.int32(i), items, kind, control)
    return xs


def full_logits(cfg, seed, tokens):
    """[n, T, V] float32: for the small sizes of the tests."""
    xs = hidden_states(cfg, seed, tokens)
    with jax.default_matmul_precision("highest"):
        return _dense._logits(xs, _dense._top32(cfg, W.seed_key(seed)), cfg)


# --- the comparison that decides a served cell's `correct` ------------------
def served_gap(cfg, seed, samples, pad_to, controls=()):
    """samples: [(prompt ids, served ids)].  The reference runs once over each
    prompt with its served tokens (teacher forced).  Returns (gaps,
    {control: gaps}), one number a served token: how far the served token's
    logit lies below the reference's best, and, for each control named,
    how far the token that the control puts first lies below it."""
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
    key, items = W.seed_key(seed), _dense._freeze_cfg(cfg)
    xs = hidden_states(cfg, seed, tokens)
    with jax.default_matmul_precision("highest"):
        best, at, _ = _dense._pick(xs, seq, pos, served, key, items, None)
    gaps = np.asarray(best - at)[:n]
    control = {}
    for c in controls:
        low = hidden_states(cfg, seed, tokens, c)
        with jax.default_matmul_precision("highest"):
            _, _, first = _dense._pick(low, seq, pos, served, key, items,
                                       _split(c)[0])
            del low
            _, at_low, _ = _dense._pick(xs, seq, pos, first, key, items, None)
        control[c] = np.asarray(best - at_low)[:n]
    return gaps, control
