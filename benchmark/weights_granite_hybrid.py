"""Seeded weights of a Granite-4.0-H hybrid decoder, made on the device.

benchmark/weights_nemotron_h.py's scheme (which stays as it is): every leaf
has a key of its own (seed, layer, leaf), matrices are the centred sum of a
random word's four bytes times a power of two, norms are 1, and the float32
vectors of a Mamba-2 layer (`dt_bias`, `A_log`, `D`) and the convolution's
taps are drawn exactly as there (its AFFINE and GAIN); the embedding is
drawn at an eighth of the matrices' std (`GAIN`, below).  The program is
handed them; the plain reference draws them again, a layer at a time.

A layer's leaves depend on its type, an entry of `layer_types`:

  mamba      in_proj [h, 2 inner + 2 G N + H], conv_w [K, C] (taps first),
             conv_b [C], dt_bias, A_log, D [H] float32, mnorm [inner],
             out_proj [inner, h]
  attention  q [h, heads D], k, v [h, kv D], o [heads D, h]

and both: ln, post_ln [h], mlp_in [h, 2 F] (gate | up), mlp_out [F, h].
The top leaves are the embedding [V, h], which is also the head
(`tie_word_embeddings`), and the final norm.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.weights import INIT_SCALE, seed_key  # noqa: F401 (the same scheme)
from benchmark.weights_nemotron_h import AFFINE, dtype_of  # noqa: F401
from benchmark.weights_nemotron_h import GAIN as _NEMOTRON_GAIN

#: the embedding, which is also the head, at 1/8 of the matrices' std: at
#: theirs, embedding_multiplier 12 makes every row's largest logit its own
#: token's (12 |E_t|^2 outweighs every other product), the model echoes its
#: input, and no fault in a layer moves a served token
GAIN = dict(_NEMOTRON_GAIN, embed=1 / 8)

_MLP = ("ln", "post_ln", "mlp_in", "mlp_out")
LEAVES = {
    "mamba": ("in_proj", "conv_w", "conv_b", "dt_bias", "A_log", "D", "mnorm",
              "out_proj") + _MLP,
    "attention": ("q", "k", "v", "o") + _MLP,
}
TOP_LEAVES = ("embed", "norm")
_ALL = tuple(dict.fromkeys(n for k in LEAVES for n in LEAVES[k])) + TOP_LEAVES
ONES = ("ln", "post_ln", "mnorm", "norm")


def sizes(cfg):
    """The numbers every consumer needs, from a config file's keys (the
    Mamba-2 ones under benchmark/reference/nemotron_h_ref.py's names)."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    H, P = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    G, N = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    types = list(cfg["layer_types"])
    if len(types) != cfg["num_hidden_layers"] or set(types) - set(LEAVES):
        raise ValueError("layer_types must give num_hidden_layers entries of "
                         "'mamba' and 'attention'")
    return dict(h=h, vocab=cfg["vocab_size"], layers=cfg["num_hidden_layers"],
                types=types, n_mamba=types.count("mamba"),
                n_attn=types.count("attention"), heads=heads,
                kv_heads=cfg["num_key_value_heads"], head_dim=h // heads,
                m_heads=H, m_head_dim=P, groups=G, state=N, inner=H * P,
                conv_k=cfg["mamba_d_conv"], conv_c=H * P + 2 * G * N,
                chunk=cfg["mamba_chunk_size"],
                ffn=cfg["shared_intermediate_size"], eps=cfg["rms_norm_eps"],
                emb_mult=cfg["embedding_multiplier"],
                res_mult=cfg["residual_multiplier"],
                attn_mult=cfg["attention_multiplier"],
                logits_scaling=cfg["logits_scaling"])


def layer_shapes(cfg, kind):
    s = sizes(cfg)
    h, F = s["h"], s["ffn"]
    mlp = {"ln": (h,), "post_ln": (h,), "mlp_in": (h, 2 * F), "mlp_out": (F, h)}
    if kind == "mamba":
        return {"in_proj": (h, 2 * s["inner"] + 2 * s["groups"] * s["state"]
                            + s["m_heads"]),
                "conv_w": (s["conv_k"], s["conv_c"]), "conv_b": (s["conv_c"],),
                "dt_bias": (s["m_heads"],), "A_log": (s["m_heads"],),
                "D": (s["m_heads"],), "mnorm": (s["inner"],),
                "out_proj": (s["inner"], h), **mlp}
    kv = s["kv_heads"] * s["head_dim"]
    return {"q": (h, s["heads"] * s["head_dim"]), "k": (h, kv), "v": (h, kv),
            "o": (s["heads"] * s["head_dim"], h), **mlp}


def leaf_shapes(cfg):
    s = sizes(cfg)
    out = {"embed": (s["vocab"], s["h"]), "norm": (s["h"],)}
    for i, kind in enumerate(s["types"]):
        for k, shp in layer_shapes(cfg, kind).items():
            out[f"layers.{i}.{k}"] = shp
    return out


def n_params(cfg):
    """Shapes only: nothing is allocated."""
    return sum(int(np.prod(s)) for s in leaf_shapes(cfg).values())


def _leaf(key_data, layer, leaf, shape, dtype):
    """`layer` is 0 for the top leaves and i + 1 for layer i; it may be traced."""
    if leaf in ONES:
        return jnp.ones(shape, dtype)
    key = jax.random.wrap_key_data(jnp.asarray(key_data, jnp.uint32),
                                   impl="threefry2x32")
    key = jax.random.fold_in(jax.random.fold_in(key, layer), _ALL.index(leaf))
    word = jax.random.bits(key, shape, jnp.uint32)
    if leaf in AFFINE:
        scale, shift = AFFINE[leaf]
        u = (word >> 16).astype(jnp.float32) * 2.0 ** -16
        return u * scale + shift  # float32 whatever the weights' dtype
    total = sum(((word >> s) & 0xFF).astype(jnp.int32) for s in (0, 8, 16, 24))
    return ((total - 510).astype(jnp.float32)
            * (INIT_SCALE * GAIN.get(leaf, 1.0))).astype(dtype)


def make_layer(key_data, cfg, i, kind):
    """Layer i's leaves by short name (`kind` its type, static; `i` may be
    traced, so one compiled program draws every layer of a type)."""
    dtype = dtype_of(cfg)
    return {k: _leaf(key_data, i + 1, k, shp, dtype)
            for k, shp in layer_shapes(cfg, kind).items()}


def make_top(key_data, cfg, names=TOP_LEAVES):
    shapes, dtype = leaf_shapes(cfg), dtype_of(cfg)
    return {k: _leaf(key_data, 0, k, shapes[k], dtype) for k in names}


# --- handing them to the program -------------------------------------------
_PROGRAM_NAMES = {
    "in_proj": "mixer.in_proj", "conv_w": "mixer.conv_weight",
    "conv_b": "mixer.conv_bias", "dt_bias": "mixer.dt_bias",
    "A_log": "mixer.A_log", "D": "mixer.D", "mnorm": "mixer.norm_weight",
    "out_proj": "mixer.out_proj", "q": "mixer.q_proj", "k": "mixer.k_proj",
    "v": "mixer.v_proj", "o": "mixer.o_proj",
    "ln": "input_layernorm.weight", "post_ln": "post_attention_layernorm.weight",
    "mlp_in": "shared_mlp.input_linear", "mlp_out": "shared_mlp.output_linear",
}


def program_name(name):
    """The benchmark's leaf name -> `GraniteHybridForCausalLM.named_parameters()`'s."""
    top = {"embed": "embed_tokens", "norm": "norm.weight"}
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
        want = jnp.float32 if n.split(".")[-1] in AFFINE else dtype
        if tuple(params[p].shape) != shapes[n] or params[p].dtype != want:
            raise RuntimeError(
                f"{p}: {params[p].shape} {params[p].dtype}, the config says "
                f"{shapes[n]} {want}")
    key = seed_key(seed)
    new = {}

    def fill(kind):
        def f(old, key_data, i):
            del old  # donated: the new leaves take their buffers
            return make_layer(key_data, cfg, i, kind)
        return jax.jit(f, donate_argnums=0, keep_unused=True)

    types = sizes(cfg)["types"]
    fills = {kind: fill(kind) for kind in set(types)}
    for i, kind in enumerate(types):
        old = {k: params.pop(to_prog[f"layers.{i}.{k}"]) for k in LEAVES[kind]}
        for k, v in fills[kind](old, key, np.int32(i)).items():
            new[to_prog[f"layers.{i}.{k}"]] = v
    top = jax.jit(lambda old, kd: make_top(kd, cfg), donate_argnums=0,
                  keep_unused=True)({k: params.pop(to_prog[k]) for k in TOP_LEAVES}, key)
    new.update({to_prog[k]: v for k, v in top.items()})
    model.load_functional_state(new)
