"""Seeded weights of a DeepSeek-V3-shaped decoder (multi-head latent
attention, gated experts), made on the device.

The scheme of benchmark/weights.py (which is Llama-shaped and stays as it
is): every leaf has a key of its own (seed, layer, leaf), matrices are the
centred sum of a random word's four bytes times 2^-13 (std 0.018, exact in
any compiled program), every norm weight is 1.  The program is handed them;
the plain reference draws them again, a layer at a time.

Every layer has the attention's leaves; its feed-forward depends on its kind
(`D` for the first `first_k_dense_replace` layers, `E` after):

  both  q [h, H (nope + rope)], kva [h, latent + rope], kvn [latent] (the
        latent's norm), kvb [latent, H (nope + v)] (a head's W^K then its
        W^V, transposed), o [H v, h], ln1, ln2 [h]
  D     gate, up [h, F], down [F, h]
  E     router [h, experts], router_bias [experts] float32, egate, eup,
        edown [held, Fe, h] (gate and up as [out, in], down as [in, out]: the
        program's layout, h minor in each), sgate, sup [h, shared Fe],
        sdown [shared Fe, h]

Matrices are [in, out].  Assumed (the source's config publishes no
initialisation; the configuration's file repeats this): matrices std 0.018;
router_bias uniform in +-1/16, a 16-bit fraction times 2^-3 less 2^-4: exact
in float32, fused or not.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.weights import INIT_SCALE, seed_key  # the same scheme

_ATTN = ("q", "kva", "kvn", "kvb", "o", "ln1", "ln2")
LEAVES = {
    "D": _ATTN + ("gate", "up", "down"),
    "E": _ATTN + ("router", "router_bias", "egate", "eup", "edown",
                  "sgate", "sup", "sdown"),
}
TOP_LEAVES = ("embed", "norm", "head")
_ALL = tuple(dict.fromkeys(LEAVES["D"] + LEAVES["E"])) + TOP_LEAVES
ONES = ("kvn", "ln1", "ln2", "norm")
#: float32 vectors: u * scale + shift, u in [0, 1) with 16 bits
AFFINE = {"router_bias": (0.125, -0.0625)}


def sizes(cfg):
    """The numbers every consumer needs, from a config file's keys."""
    lo, hi = cfg.get("share", {}).get("experts_held", (0, cfg["n_routed_experts"]))
    layers, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    s = dict(h=cfg["hidden_size"], vocab=cfg["vocab_size"], layers=layers,
             kinds="D" * dense + "E" * (layers - dense),
             heads=cfg["num_attention_heads"], nope=cfg["qk_nope_head_dim"],
             rope=cfg["qk_rope_head_dim"], v=cfg["v_head_dim"],
             latent=cfg["kv_lora_rank"], theta=cfg["rope_theta"],
             eps=cfg["rms_norm_eps"], ffn=cfg["intermediate_size"],
             expert_ffn=cfg["moe_intermediate_size"],
             shared_ffn=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
             router=cfg["n_routed_experts"], held=(int(lo), int(hi)),
             n_held=int(hi) - int(lo), top_k=cfg["num_experts_per_tok"],
             scaling=cfg["routed_scaling_factor"])
    if not 0 <= dense <= layers or cfg["q_lora_rank"] is not None \
            or cfg["n_group"] != 1 or cfg["rope_scaling"] is not None \
            or cfg["num_key_value_heads"] != s["heads"]:
        raise ValueError("the configuration uses a key this benchmark does not "
                         "implement (q_lora_rank, n_group, rope_scaling, GQA)")
    return s


def dtype_of(cfg):
    return jnp.dtype(cfg["torch_dtype"])


def layer_shapes(cfg, kind):
    s = sizes(cfg)
    h, H = s["h"], s["heads"]
    out = {"q": (h, H * (s["nope"] + s["rope"])), "kva": (h, s["latent"] + s["rope"]),
           "kvn": (s["latent"],), "kvb": (s["latent"], H * (s["nope"] + s["v"])),
           "o": (H * s["v"], h), "ln1": (h,), "ln2": (h,)}
    if kind == "D":
        out.update(gate=(h, s["ffn"]), up=(h, s["ffn"]), down=(s["ffn"], h))
    else:
        e = (s["n_held"], s["expert_ffn"], h)
        out.update(router=(h, s["router"]), router_bias=(s["router"],),
                   egate=e, eup=e, edown=e, sgate=(h, s["shared_ffn"]),
                   sup=(h, s["shared_ffn"]), sdown=(s["shared_ffn"], h))
    return out


def leaf_shapes(cfg):
    s = sizes(cfg)
    out = {"embed": (s["vocab"], s["h"]), "norm": (s["h"],),
           "head": (s["h"], s["vocab"])}
    for i, kind in enumerate(s["kinds"]):
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
    return ((total - 510).astype(jnp.float32) * INIT_SCALE).astype(dtype)


def make_layer(key_data, cfg, i, kind):
    """Layer i's leaves by short name (`kind` static; `i` may be traced, so
    one compiled program draws every layer of a kind)."""
    dtype = dtype_of(cfg)
    return {k: _leaf(key_data, i + 1, k, shp, dtype)
            for k, shp in layer_shapes(cfg, kind).items()}


def make_top(key_data, cfg, names=TOP_LEAVES):
    shapes, dtype = leaf_shapes(cfg), dtype_of(cfg)
    return {k: _leaf(key_data, 0, k, shapes[k], dtype) for k in names}


# --- handing them to the program -------------------------------------------
_PROGRAM_NAMES = {
    "q": "self_attn.q_proj", "kva": "self_attn.kv_a_proj_with_mqa",
    "kvn": "self_attn.kv_a_layernorm", "kvb": "self_attn.kv_b_proj",
    "o": "self_attn.o_proj", "ln1": "input_layernorm.weight",
    "ln2": "post_attention_layernorm.weight", "gate": "mlp.gate_proj",
    "up": "mlp.up_proj", "down": "mlp.down_proj", "router": "mlp.gate_weight",
    "router_bias": "mlp.e_score_correction_bias", "egate": "mlp.experts_gate",
    "eup": "mlp.experts_up", "edown": "mlp.experts_down",
    "sgate": "mlp.shared_experts.gate_proj", "sup": "mlp.shared_experts.up_proj",
    "sdown": "mlp.shared_experts.down_proj",
}


def program_name(name):
    """The benchmark's leaf name -> `DeepseekV3ForCausalLM.named_parameters()`'s."""
    top = {"embed": "embed_tokens", "norm": "norm.weight", "head": "lm_head"}
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

    kinds = sizes(cfg)["kinds"]
    fills = {kind: fill(kind) for kind in set(kinds)}
    for i, kind in enumerate(kinds):
        old = {k: params.pop(to_prog[f"layers.{i}.{k}"]) for k in LEAVES[kind]}
        for k, v in fills[kind](old, key, np.int32(i)).items():
            new[to_prog[f"layers.{i}.{k}"]] = v
    top = jax.jit(lambda old, kd: make_top(kd, cfg), donate_argnums=0,
                  keep_unused=True)({k: params.pop(to_prog[k]) for k in TOP_LEAVES}, key)
    new.update({to_prog[k]: v for k, v in top.items()})
    model.load_functional_state(new)
