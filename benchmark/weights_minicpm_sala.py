"""Seeded weights of a MiniCPM-SALA decoder, made on the device.

The scheme of benchmark/weights.py (which is Llama-shaped and stays as it
is): every leaf has a key of its own (seed, layer, leaf), matrices are the
centred sum of a random word's four bytes times 2^-13 (std 0.018, exact in
any compiled program), every norm weight is 1.  The program is handed them;
the plain reference draws them again, a layer at a time.

A layer's leaves depend on its mixer (`mixer_types`):

  lightning-attn  q, k, v, g [h, 32 x 128], o [32 x 128, h], qn, kn [128]
                  (the norm a head), on [32 x 128] (the norm over the
                  concatenated heads)
  minicpm4        q, g [h, 32 x 128], k, v [h, 2 x 128], o [32 x 128, h],
                  qn, kn [128]
  both            gate, up [h, F], down [F, h], ln1, ln2 [h]

Matrices are [in, out].  Assumed: the source's config publishes no
initialisation; the configuration's file repeats this.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.weights import INIT_SCALE, seed_key  # the same scheme

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"
_FFN = ("gate", "up", "down", "ln1", "ln2")
LEAVES = {
    LIGHTNING: ("q", "k", "v", "g", "o", "qn", "kn", "on") + _FFN,
    SPARSE: ("q", "k", "v", "g", "o", "qn", "kn") + _FFN,
}
TOP_LEAVES = ("embed", "norm", "head")
_ALL = LEAVES[LIGHTNING] + TOP_LEAVES
ONES = ("qn", "kn", "on", "ln1", "ln2", "norm")


def sizes(cfg):
    """The numbers every consumer needs, from a config file's keys."""
    s = dict(h=cfg["hidden_size"], ffn=cfg["intermediate_size"],
             vocab=cfg["vocab_size"], layers=cfg["num_hidden_layers"],
             mixers=tuple(cfg["mixer_types"]), heads=cfg["num_attention_heads"],
             kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
             l_heads=cfg["lightning_nh"], l_head_dim=cfg["lightning_head_dim"],
             theta=cfg["rope_theta"], eps=cfg["rms_norm_eps"],
             scale_emb=cfg["scale_emb"],
             residual=cfg["scale_depth"]
             / cfg.get("published", cfg)["num_hidden_layers"] ** 0.5,
             head_div=cfg["hidden_size"] / cfg["dim_model_base"],
             sparse=dict(cfg["sparse_config"]))
    s["n_sparse"] = s["mixers"].count(SPARSE)
    s["n_lightning"] = s["mixers"].count(LIGHTNING)
    if len(s["mixers"]) != s["layers"] or s["n_sparse"] + s["n_lightning"] != s["layers"] \
            or cfg["lightning_nkv"] != cfg["lightning_nh"]:
        raise ValueError("mixer_types disagrees with num_hidden_layers, or "
                         "lightning_nkv with lightning_nh")
    return s


def dtype_of(cfg):
    return jnp.dtype(cfg["torch_dtype"])


def layer_shapes(cfg, kind):
    s = sizes(cfg)
    h, F = s["h"], s["ffn"]
    ffn = {"gate": (h, F), "up": (h, F), "down": (F, h), "ln1": (h,), "ln2": (h,)}
    if kind == LIGHTNING:
        inner, D = s["l_heads"] * s["l_head_dim"], s["l_head_dim"]
        return {"q": (h, inner), "k": (h, inner), "v": (h, inner), "g": (h, inner),
                "o": (inner, h), "qn": (D,), "kn": (D,), "on": (inner,), **ffn}
    D = s["head_dim"]
    return {"q": (h, s["heads"] * D), "k": (h, s["kv_heads"] * D),
            "v": (h, s["kv_heads"] * D), "g": (h, s["heads"] * D),
            "o": (s["heads"] * D, h), "qn": (D,), "kn": (D,), **ffn}


def leaf_shapes(cfg):
    s = sizes(cfg)
    out = {"embed": (s["vocab"], s["h"]), "norm": (s["h"],),
           "head": (s["h"], s["vocab"])}
    for i, kind in enumerate(s["mixers"]):
        for k, shp in layer_shapes(cfg, kind).items():
            out[f"layers.{i}.{k}"] = shp
    return out


def n_params(cfg):
    """Shapes only: nothing is allocated."""
    return sum(int(np.prod(s)) for s in leaf_shapes(cfg).values())


def matmul_params(cfg):
    """Parameters of the matrices a token goes through (norms and the
    embedding's gather apart), with the head's."""
    return sum(int(np.prod(s)) for n, s in leaf_shapes(cfg).items()
               if len(s) == 2 and n != "embed")


def _leaf(key_data, layer, leaf, shape, dtype):
    """`layer` is 0 for the top leaves and i + 1 for layer i; it may be traced."""
    if leaf in ONES:
        return jnp.ones(shape, dtype)
    key = jax.random.wrap_key_data(jnp.asarray(key_data, jnp.uint32),
                                   impl="threefry2x32")
    key = jax.random.fold_in(jax.random.fold_in(key, layer), _ALL.index(leaf))
    word = jax.random.bits(key, shape, jnp.uint32)
    total = sum(((word >> s) & 0xFF).astype(jnp.int32) for s in (0, 8, 16, 24))
    return ((total - 510).astype(jnp.float32) * INIT_SCALE).astype(dtype)


def make_layer(key_data, cfg, i, kind):
    """Layer i's leaves by short name (`kind` its mixer, static; `i` may be
    traced, so one compiled program draws every layer of a kind)."""
    dtype = dtype_of(cfg)
    return {k: _leaf(key_data, i + 1, k, shp, dtype)
            for k, shp in layer_shapes(cfg, kind).items()}


def make_top(key_data, cfg, names=TOP_LEAVES):
    shapes, dtype = leaf_shapes(cfg), dtype_of(cfg)
    return {k: _leaf(key_data, 0, k, shapes[k], dtype) for k in names}


# --- handing them to the program -------------------------------------------
_PROGRAM_NAMES = {
    "q": "mixer.q_proj", "k": "mixer.k_proj", "v": "mixer.v_proj",
    "g": "mixer.g_proj", "o": "mixer.o_proj", "qn": "mixer.q_norm",
    "kn": "mixer.k_norm", "on": "mixer.o_norm", "gate": "mlp.gate_proj",
    "up": "mlp.up_proj", "down": "mlp.down_proj", "ln1": "input_norm.weight",
    "ln2": "post_norm.weight",
}


def program_name(name):
    """The benchmark's leaf name -> `MiniCPMSALAForCausalLM.named_parameters()`'s."""
    top = {"embed": "embed_tokens", "norm": "norm_f.weight", "head": "lm_head"}
    if name in top:
        return top[name]
    _, i, leaf = name.split(".")
    return f"layers.{i}.{_PROGRAM_NAMES[leaf]}"


def load_into(model, cfg, seed):
    """Overwrite every parameter of `model`, a layer a jitted call that
    donates the old leaves: the peak is one copy of the weights plus one
    layer, never a float32 copy."""
    params, _ = model.functional_state()
    shapes, dtype = leaf_shapes(cfg), dtype_of(cfg)
    to_prog = {n: program_name(n) for n in shapes}
    if set(to_prog.values()) ^ set(params):
        odd = sorted(set(to_prog.values()) ^ set(params))[:6]
        raise RuntimeError(f"parameter names differ from the program's: {odd}")
    for n, p in to_prog.items():
        if tuple(params[p].shape) != shapes[n] or params[p].dtype != dtype:
            raise RuntimeError(
                f"{p}: {params[p].shape} {params[p].dtype}, the config says "
                f"{shapes[n]} {dtype}")
    key = seed_key(seed)
    new = {}

    def fill(kind):
        def f(old, key_data, i):
            del old  # donated: the new leaves take their buffers
            return make_layer(key_data, cfg, i, kind)
        return jax.jit(f, donate_argnums=0, keep_unused=True)

    mixers = sizes(cfg)["mixers"]
    fills = {kind: fill(kind) for kind in set(mixers)}
    for i, kind in enumerate(mixers):
        old = {k: params.pop(to_prog[f"layers.{i}.{k}"]) for k in LEAVES[kind]}
        for k, v in fills[kind](old, key, np.int32(i)).items():
            new[to_prog[f"layers.{i}.{k}"]] = v
    top = jax.jit(lambda old, kd: make_top(kd, cfg), donate_argnums=0,
                  keep_unused=True)({k: params.pop(to_prog[k]) for k in TOP_LEAVES}, key)
    new.update({to_prog[k]: v for k, v in top.items()})
    model.load_functional_state(new)
