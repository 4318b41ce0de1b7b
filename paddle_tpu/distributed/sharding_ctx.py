"""Mesh/sharding context shared by distributed layers and train steps.

The scaling-book recipe: pick a Mesh, annotate shardings on params/activations, let
XLA's SPMD partitioner insert collectives.  Layers record a `sharding_spec` tuple on
their Parameters (e.g. ColumnParallelLinear weight -> (None, 'mp')); ShardedTrainStep
turns specs into NamedShardings.  `with_sharding_constraint` is a no-op outside a mesh
context so the same layer code runs eagerly on one chip.
"""
from __future__ import annotations

import contextlib
import math

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_current_mesh: list = []


@contextlib.contextmanager
def mesh_scope(mesh: Mesh):
    _current_mesh.append(mesh)
    try:
        with mesh:
            yield mesh
    finally:
        _current_mesh.pop()


def current_mesh() -> Mesh | None:
    if _current_mesh:
        return _current_mesh[-1]
    return None


# ------------------------------------------------- Pallas kernels under a mesh
#
# GSPMD cannot partition a Mosaic custom call: a Pallas kernel traced under
# plain jit with inputs sharded over more than one REAL chip fails at lowering
# ("Mosaic kernels cannot be automatically partitioned").  On the virtual CPU
# mesh kernels run in interpret mode, which lowers to ordinary HLO, so only a
# TPU mesh (or the chipless AOT compile in tests/test_chip_smoke.py) shows
# it.  Kernel dispatch sites therefore run the call per shard.
#
# A layout is one character per array dim: "b" = batch, split over the data
# axes the mesh has (dp, sharding); "h" = heads, split over mp; "-" =
# replicated.  "" replicates the whole argument (seeds, norm weights).

def _kernel_mesh():
    """(active mesh, {layout char: mesh axes splitting such a dim}) when a
    mesh of more than one device is active, else (None, None)."""
    mesh = current_mesh()
    if mesh is None or mesh.size == 1:
        return None, None

    def on(*names):
        return tuple(a for a in names
                     if a in mesh.axis_names and mesh.shape[a] > 1)

    return mesh, {"b": on("dp", "sharding"), "h": on("mp"), "-": ()}


def local_shape(shape, layout):
    """The per-shard shape shard_kernel hands the kernel for an argument of
    `shape` under `layout` (== `shape` with no multi-device mesh active) —
    what a dispatch site feeds its kernel's `supported(...)` check.  A dim
    its mesh axes do not divide is a ValueError: the kernel path never
    detours to dense math over a shape mismatch."""
    mesh, axes = _kernel_mesh()
    out = list(shape)
    for d, c in enumerate(layout if mesh is not None else ""):
        n = math.prod(mesh.shape[a] for a in axes[c])
        if out[d] % n:
            raise ValueError(
                f"kernel argument of shape {tuple(shape)}: dim {d} "
                f"({'batch' if c == 'b' else 'heads'}) = {out[d]} is not "
                f"divisible by mesh axes {axes[c]} of total size {n}")
        out[d] //= n
    return tuple(out)


def shard_kernel(fn, in_layouts, out_layout):
    """`fn` wrapped to run once per shard of the active mesh
    (`jax.shard_map`, check_vma=False: pallas_call outputs carry no
    varying-axes annotation); `fn` itself when no mesh of more than one
    device is active."""
    mesh, axes = _kernel_mesh()
    if mesh is None:
        return fn

    def spec(layout):
        return P(*(axes[c] or None for c in layout))

    sharded = jax.shard_map(fn, mesh=mesh,
                            in_specs=tuple(map(spec, in_layouts)),
                            out_specs=spec(out_layout), check_vma=False)

    def call(*args):
        for a, layout in zip(args, in_layouts):
            local_shape(a.shape, layout)  # raises on a non-dividing dim
        return sharded(*args)

    return call


def shard_index():
    """Inside a shard_kernel body: this shard's linear index over the axes
    shard_kernel splits on (0 when it is the identity).  Dropout kernels add
    it to their seed — the in-kernel block id restarts at 0 on every shard,
    so without it all shards would draw the same masks."""
    mesh, axes = _kernel_mesh()
    names = axes["b"] + axes["h"] if mesh is not None else ()
    return jax.lax.axis_index(names) if names else 0


def constraint(x, *spec):
    """Apply a sharding constraint if a mesh is active and x is traced."""
    mesh = current_mesh()
    if mesh is None or not isinstance(x, jax.core.Tracer):
        return x
    # drop axis names the mesh doesn't have (e.g. running tp code on a dp-only mesh)
    clean = tuple(s if (s is None or _axes_in(mesh, s)) else None for s in spec)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*clean)))


def _axes_in(mesh, s):
    names = mesh.axis_names
    if isinstance(s, (tuple, list)):
        return all(n in names for n in s)
    return s in names


def param_sharding(mesh: Mesh, spec):
    if spec is None:
        return NamedSharding(mesh, P())
    clean = tuple(s if (s is None or _axes_in(mesh, s)) else None for s in spec)
    return NamedSharding(mesh, P(*clean))


def annotate(param, *spec):
    """Record the logical sharding of a Parameter (consumed by ShardedTrainStep)."""
    param.sharding_spec = tuple(spec)
    return param
