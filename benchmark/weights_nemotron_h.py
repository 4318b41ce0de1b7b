"""Seeded weights of a Nemotron-H hybrid decoder, made on the device.

The scheme of benchmark/weights.py (which is Llama-shaped and stays as it
is): every leaf has a key of its own (seed, layer, leaf), matrices are the
centred sum of a random word's four bytes times a power of two (exact in any
compiled program), norms are 1.  The program is handed them; the plain
reference draws them again, a layer at a time.

A layer's leaves depend on its kind, a character of the pattern:

  M  in_proj [h, 2 inner + 2 G N + H], conv_w [K, C] (taps first),
     conv_b [C], dt_bias, A_log, D [H] float32, mnorm [inner],
     out_proj [inner, h], ln [h]
  E  router [h, published experts], router_bias [experts] float32,
     up, down [held, F, h] (up as [out, in], down as [in, out]: the program's
     layout, h minor in both), shared_up [h, Fs], shared_down [Fs, h], ln
  *  q [h, heads D], k, v [h, kv D], o [heads D, h], ln

Assumed (the source publishes no initialisation beyond `time_step_min/max`;
the configuration's file repeats this): matrices std 0.018; conv_w 16 times
that (0.29, the depthwise convolution's usual uniform(+-1/2)); dt_bias
uniform in [-7, -2.25] = softplus^-1 of [0.0009, 0.1], the config's
time_step_min..max; A_log uniform in [0, 2.75] (A in -[1, 15.6]); D in
[0.5, 1.5); router_bias in +-1/16.  Each is u x (a constant of few bits) + c
with u a 16-bit fraction: exact in float32, fused or not.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.weights import INIT_SCALE, seed_key  # the same scheme

LEAVES = {
    "M": ("in_proj", "conv_w", "conv_b", "dt_bias", "A_log", "D", "mnorm",
          "out_proj", "ln"),
    "E": ("router", "router_bias", "up", "down", "shared_up", "shared_down", "ln"),
    "*": ("q", "k", "v", "o", "ln"),
}
TOP_LEAVES = ("embed", "norm", "head")
_ALL = tuple(dict.fromkeys(n for k in "ME*" for n in LEAVES[k])) + TOP_LEAVES
ONES = ("ln", "mnorm", "norm")
#: float32 vectors: u * scale + shift, u in [0, 1) with 16 bits
AFFINE = {"dt_bias": (4.75, -7.0), "A_log": (2.75, 0.0), "D": (1.0, 0.5),
          "router_bias": (0.125, -0.0625)}
GAIN = {"conv_w": 16.0}


def sizes(cfg):
    """The numbers every consumer needs, from a config file's keys."""
    share = cfg.get("share", {})
    lo, hi = share.get("experts_held", (0, cfg["n_routed_experts"]))
    heads, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    G, N = cfg["n_groups"], cfg["ssm_state_size"]
    s = dict(h=cfg["hidden_size"], vocab=cfg["vocab_size"],
             layers=cfg["num_hidden_layers"], pattern=cfg["hybrid_override_pattern"],
             heads=cfg["num_attention_heads"], kv_heads=cfg["num_key_value_heads"],
             head_dim=cfg["head_dim"], m_heads=heads, m_head_dim=P, groups=G,
             state=N, inner=heads * P, conv_k=cfg["conv_kernel"],
             conv_c=heads * P + 2 * G * N, chunk=cfg["chunk_size"],
             router=share.get("router_experts", cfg["n_routed_experts"]),
             held=(int(lo), int(hi)), n_held=int(hi) - int(lo),
             top_k=cfg["num_experts_per_tok"], ffn=cfg["moe_intermediate_size"],
             shared_ffn=cfg["moe_shared_expert_intermediate_size"],
             scaling=cfg["routed_scaling_factor"], eps=cfg["layer_norm_epsilon"])
    if len(s["pattern"]) != s["layers"] or s["n_held"] != cfg["n_routed_experts"]:
        raise ValueError("the pattern's length or the held experts disagree "
                         "with num_hidden_layers / n_routed_experts")
    return s


def dtype_of(cfg):
    return jnp.dtype(cfg["torch_dtype"])


def layer_shapes(cfg, kind):
    s = sizes(cfg)
    h = s["h"]
    if kind == "M":
        return {"in_proj": (h, 2 * s["inner"] + 2 * s["groups"] * s["state"]
                            + s["m_heads"]),
                "conv_w": (s["conv_k"], s["conv_c"]), "conv_b": (s["conv_c"],),
                "dt_bias": (s["m_heads"],), "A_log": (s["m_heads"],),
                "D": (s["m_heads"],), "mnorm": (s["inner"],),
                "out_proj": (s["inner"], h), "ln": (h,)}
    if kind == "E":
        return {"router": (h, s["router"]), "router_bias": (s["router"],),
                "up": (s["n_held"], s["ffn"], h), "down": (s["n_held"], s["ffn"], h),
                "shared_up": (h, s["shared_ffn"]), "shared_down": (s["shared_ffn"], h),
                "ln": (h,)}
    kv = s["kv_heads"] * s["head_dim"]
    return {"q": (h, s["heads"] * s["head_dim"]), "k": (h, kv), "v": (h, kv),
            "o": (s["heads"] * s["head_dim"], h), "ln": (h,)}


def leaf_shapes(cfg):
    s = sizes(cfg)
    out = {"embed": (s["vocab"], s["h"]), "norm": (s["h"],),
           "head": (s["h"], s["vocab"])}
    for i, kind in enumerate(s["pattern"]):
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
    """Layer i's leaves by short name (`kind` its pattern character, static;
    `i` may be traced, so one compiled program draws every layer of a kind)."""
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
    "out_proj": "mixer.out_proj", "ln": "norm.weight",
    "router": "mixer.gate_weight", "router_bias": "mixer.e_score_correction_bias",
    "up": "mixer.experts_up", "down": "mixer.experts_down",
    "shared_up": "mixer.shared_up", "shared_down": "mixer.shared_down",
    "q": "mixer.q_proj", "k": "mixer.k_proj", "v": "mixer.v_proj",
    "o": "mixer.o_proj",
}


def program_name(name):
    """The benchmark's leaf name -> `NemotronHForCausalLM.named_parameters()`'s."""
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

    fills = {kind: fill(kind) for kind in set(sizes(cfg)["pattern"])}
    for i, kind in enumerate(sizes(cfg)["pattern"]):
        old = {k: params.pop(to_prog[f"layers.{i}.{k}"]) for k in LEAVES[kind]}
        for k, v in fills[kind](old, key, np.int32(i)).items():
            new[to_prog[f"layers.{i}.{k}"]] = v
    top = jax.jit(lambda old, kd: make_top(kd, cfg), donate_argnums=0,
                  keep_unused=True)({k: params.pop(to_prog[k]) for k in TOP_LEAVES}, key)
    new.update({to_prog[k]: v for k, v in top.items()})
    model.load_functional_state(new)
