"""Seeded weights of a Granite-4.0-H decoder with routed experts (Small),
made on the device.

benchmark/weights_granite_hybrid.py's scheme (which stays as it is) for every
leaf Micro has, under the same keys; every layer adds

  router [h, router experts], e_gate, e_up, e_down [held, F, h]

(e_gate and e_up as [out, in], e_down as [in, out]: the program's layout, h
minor in all three).  A routed expert is drawn by its index among ALL the
router's experts, so a chip that holds experts [lo, hi) holds exactly the
matrices the uncut model has there, and two shares add up to the whole.  The
program is handed them; the plain reference draws them again, a layer at a
time.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights_granite_hybrid as _H
from benchmark.weights import INIT_SCALE, seed_key
from benchmark.weights_nemotron_h import AFFINE, dtype_of

EXPERTS = ("e_gate", "e_up", "e_down")
_MOE = ("router",) + EXPERTS
LEAVES = {kind: names + _MOE for kind, names in _H.LEAVES.items()}
TOP_LEAVES = _H.TOP_LEAVES
_ALL = _H._ALL + _MOE   # Micro's leaves keep their keys
ONES, GAIN = _H.ONES, _H.GAIN


def sizes(cfg):
    """weights_granite_hybrid's numbers, and the experts': the router's
    width, the held range, top_k, one expert's width."""
    s = _H.sizes(cfg)
    share = cfg.get("share", {})
    lo, hi = share.get("experts_held", (0, cfg["num_local_experts"]))
    s.update(router=share.get("router_experts", cfg["num_local_experts"]),
             held=(int(lo), int(hi)), n_held=int(hi) - int(lo),
             top_k=cfg["num_experts_per_tok"], expert_ffn=cfg["intermediate_size"])
    if s["n_held"] != cfg["num_local_experts"]:
        raise ValueError("share.experts_held disagrees with num_local_experts")
    return s


def layer_shapes(cfg, kind):
    s = sizes(cfg)
    h, F, n = s["h"], s["expert_ffn"], s["n_held"]
    return {**_H.layer_shapes(cfg, kind), "router": (h, s["router"]),
            **{k: (n, F, h) for k in EXPERTS}}


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


def _leaf(key_data, layer, leaf, shape, dtype, lo=0):
    """`layer` is 0 for the top leaves and i + 1 for layer i; it may be
    traced.  An expert leaf's first axis is experts lo, lo + 1, ..."""
    if leaf in ONES:
        return jnp.ones(shape, dtype)
    key = jax.random.wrap_key_data(jnp.asarray(key_data, jnp.uint32),
                                   impl="threefry2x32")
    key = jax.random.fold_in(jax.random.fold_in(key, layer), _ALL.index(leaf))
    if leaf in EXPERTS:  # each expert a key of its own
        word = jax.vmap(lambda e: jax.random.bits(
            jax.random.fold_in(key, e), shape[1:], jnp.uint32))(
                jnp.arange(lo, lo + shape[0]))
    else:
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
    dtype, lo = dtype_of(cfg), sizes(cfg)["held"][0]
    return {k: _leaf(key_data, i + 1, k, shp, dtype, lo)
            for k, shp in layer_shapes(cfg, kind).items()}


def make_top(key_data, cfg, names=TOP_LEAVES):
    shapes, dtype = leaf_shapes(cfg), dtype_of(cfg)
    return {k: _leaf(key_data, 0, k, shapes[k], dtype) for k in names}


# --- handing them to the program -------------------------------------------
_PROGRAM_NAMES = {
    **_H._PROGRAM_NAMES, "router": "block_sparse_moe.gate_weight",
    "e_gate": "block_sparse_moe.experts_gate",
    "e_up": "block_sparse_moe.experts_up", "e_down": "block_sparse_moe.experts_down",
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
