"""Seeded weights of a Llama-shaped decoder, made on the device.

The benchmark makes the weights, the program is handed them, and the plain
reference makes the same ones again from the seed, a layer at a time: every
leaf has a key of its own (seed, layer, leaf), so any part can be drawn alone
and threefry gives the same bits whatever program the draw is compiled into.

Names are the benchmark's own: "embed", "norm", "head" and
"layers.<i>.<q|k|v|o|gate|up|down|ln1|ln2>".  Matrices are [in, out].
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

#: Matrices are a sum of the four bytes of a random word, centred, times
#: 2**-13: integers, one exact conversion and a power of two, so every
#: compiled program, fused however, draws the same bits.  The standard
#: deviation is sqrt(4 * (256**2 - 1) / 12) * 2**-13 = 0.01804; both sources
#: state an `initializer_range` of 0.02.
INIT_SCALE = 2.0 ** -13
INIT_STD = (4 * (256 ** 2 - 1) / 12) ** 0.5 * INIT_SCALE
LAYER_LEAVES = ("q", "k", "v", "o", "gate", "up", "down", "ln1", "ln2")
TOP_LEAVES = ("embed", "norm", "head")


def sizes(cfg):
    """The few numbers every consumer needs, from a config file's keys."""
    h = cfg["hidden_size"]
    heads = cfg["num_attention_heads"]
    return dict(h=h, ffn=cfg["intermediate_size"], heads=heads,
                kv_heads=cfg["num_key_value_heads"], head_dim=h // heads,
                vocab=cfg["vocab_size"], layers=cfg["num_hidden_layers"])


def dtype_of(cfg):
    """The type the weights are held in: the config's `torch_dtype`."""
    return jnp.dtype(cfg["torch_dtype"])


def leaf_shapes(cfg):
    s = sizes(cfg)
    kv = s["kv_heads"] * s["head_dim"]
    layer = {"q": (s["h"], s["h"]), "k": (s["h"], kv), "v": (s["h"], kv),
             "o": (s["h"], s["h"]), "gate": (s["h"], s["ffn"]),
             "up": (s["h"], s["ffn"]), "down": (s["ffn"], s["h"]),
             "ln1": (s["h"],), "ln2": (s["h"],)}
    out = {"embed": (s["vocab"], s["h"]), "norm": (s["h"],),
           "head": (s["h"], s["vocab"])}
    for i in range(s["layers"]):
        for k, shp in layer.items():
            out[f"layers.{i}.{k}"] = shp
    return out


def n_params(cfg):
    return sum(int(np.prod(s)) for s in leaf_shapes(cfg).values())


def seed_key(seed):
    """A threefry key from any whole number up to 2**64: the seed's two
    32-bit halves ARE the key, so nothing overflows a signed int."""
    seed = int(seed)
    if not 0 <= seed < 2**64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    return np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32)


def _leaf(key_data, layer, leaf, shape, dtype):
    """`layer` is 0 for the top leaves and i + 1 for layer i; it may be traced."""
    if leaf in ("ln1", "ln2", "norm"):
        return jnp.ones(shape, dtype)
    key = jax.random.wrap_key_data(jnp.asarray(key_data, jnp.uint32),
                                   impl="threefry2x32")
    key = jax.random.fold_in(jax.random.fold_in(key, layer),
                             (LAYER_LEAVES + TOP_LEAVES).index(leaf))
    word = jax.random.bits(key, shape, jnp.uint32)
    total = sum(((word >> s) & 0xFF).astype(jnp.int32) for s in (0, 8, 16, 24))
    return ((total - 510).astype(jnp.float32) * INIT_SCALE).astype(dtype)


def make_layer(key_data, cfg, i):
    """Layer i's leaves by short name; `i` may be a traced integer, so one
    compiled program draws any layer."""
    shapes, dtype = leaf_shapes(cfg), dtype_of(cfg)
    return {k: _leaf(key_data, i + 1, k, shapes[f"layers.0.{k}"], dtype)
            for k in LAYER_LEAVES}


def make_top(key_data, cfg, names=TOP_LEAVES):
    shapes, dtype = leaf_shapes(cfg), dtype_of(cfg)
    return {k: _leaf(key_data, 0, k, shapes[k], dtype) for k in names}


def make(key_data, cfg):
    """{name: array} of every leaf; trace under jit."""
    out = make_top(key_data, cfg)
    for i in range(cfg["num_hidden_layers"]):
        for k, v in make_layer(key_data, cfg, i).items():
            out[f"layers.{i}.{k}"] = v
    return out


# --- handing them to the program -------------------------------------------
_PROGRAM_NAMES = {
    "q": "self_attn.q_proj.weight", "k": "self_attn.k_proj.weight",
    "v": "self_attn.v_proj.weight", "o": "self_attn.o_proj.weight",
    "gate": "mlp.gate_proj.weight", "up": "mlp.up_proj.weight",
    "down": "mlp.down_proj.weight", "ln1": "input_layernorm.weight",
    "ln2": "post_attention_layernorm.weight",
}


def program_name(name):
    """The benchmark's leaf name -> `LlamaForCausalLM.named_parameters()`'s."""
    if name == "embed":
        return "llama.embed_tokens.weight"
    if name == "norm":
        return "llama.norm.weight"
    if name == "head":
        return "lm_head.weight"
    _, i, leaf = name.split(".")
    return f"llama.layers.{i}.{_PROGRAM_NAMES[leaf]}"


def load_into(model, cfg, seed):
    """Overwrite every parameter of `model` in ONE jitted call that donates
    the old ones, so the peak is one copy of the weights."""
    params, _ = model.functional_state()
    shapes = leaf_shapes(cfg)
    to_prog = {n: program_name(n) for n in shapes}
    missing = set(to_prog.values()) ^ set(params)
    if missing:
        raise RuntimeError(f"parameter names differ from the program's: {sorted(missing)[:6]}")
    for n, p in to_prog.items():
        if tuple(params[p].shape) != shapes[n]:
            raise RuntimeError(f"{p}: shape {params[p].shape}, config says {shapes[n]}")
    dtypes = {str(v.dtype) for v in params.values()}
    if dtypes != {str(dtype_of(cfg))}:
        raise RuntimeError(f"the model holds {dtypes}, the config says {dtype_of(cfg)}")

    def fill(old, key_data):
        del old  # donated: the new leaves take their buffers
        new = make(key_data, cfg)
        return {to_prog[n]: v for n, v in new.items()}

    new = jax.jit(fill, donate_argnums=0, keep_unused=True)(params, seed_key(seed))
    model.load_functional_state(new)
